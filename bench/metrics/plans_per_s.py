"""Plans completed in the closed loop's window over the window's length."""


def read(rec):
    done = [op for op in rec.ops
            if op["kind"] == "plan" and op.get("answer") is not None]
    return len(done) / rec.window_s if done and rec.window_s > 0 else None
