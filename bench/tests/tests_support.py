"""Drive a cell's set-up and window in this process, on any backend."""


def drive(cell, seed: int, seconds: float):
    logs = []
    cli = cell.client().Client(cell.config, cell.traffic, seed, logs.append)
    cli.setup(seconds)
    return cli, cli.window(seconds)
