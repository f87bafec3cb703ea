"""Nothing can follow the last line.

At start the run duplicates the real stdout descriptor and points
descriptor 1 at a log file, so whatever the program, libtpu or the profiler
print (epoch lines, ``METRICS_JSON``, warnings written to fd 1 from C++)
lands in the log. The result is written once to the saved descriptor and the
process leaves with ``os._exit`` while the trainer threads are still alive:
no ``atexit`` hook, no late epoch line, no interpreter shutdown can add a
byte after it.
"""

from __future__ import annotations

import json
import os
import sys


class StdoutGuard:
    def __init__(self, log_path: str):
        os.makedirs(os.path.dirname(log_path), exist_ok=True)
        sys.stdout.flush()
        self._real_fd = os.dup(1)
        log_fd = os.open(log_path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC,
                         0o644)
        os.dup2(log_fd, 1)
        os.close(log_fd)
        # sys.stdout still wraps descriptor 1, which is now the log; a pipe
        # made it block-buffered, and the drivers tail this file.
        sys.stdout.reconfigure(line_buffering=True)
        self.log_path = log_path

    def emit_and_exit(self, result: dict) -> None:
        """Write the one result line to the real stdout and leave."""
        try:
            data = (json.dumps(result, allow_nan=False) + "\n").encode()
        except (TypeError, ValueError) as e:
            self.fail(f"the result cannot be written as JSON: {e}", 2)
        sys.stdout.flush()
        sys.stderr.flush()
        while data:
            data = data[os.write(self._real_fd, data):]
        os._exit(0)

    @staticmethod
    def fail(reason: str, code: int = 1) -> None:
        """Leave without a result line: reason on stderr, non-zero exit."""
        sys.stdout.flush()
        sys.stderr.write(f"benchmark: {reason}\n")
        sys.stderr.flush()
        os._exit(code)
