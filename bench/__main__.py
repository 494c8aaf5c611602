import time

_T0 = time.perf_counter()

import sys  # noqa: E402

from bench.run import main, since_process_start  # noqa: E402

if __name__ == "__main__":
    # the process's age when _T0 was read
    started = since_process_start() - (time.perf_counter() - _T0)
    sys.exit(main(started=max(started, 0.0), t0=_T0))
