"""Run a command and report its peak RSS — a `/usr/bin/time -v` stand-in
(the image ships no GNU time), so a host-side run can state its peak
memory alongside its wall clock.

Usage: python scripts/rss_wrap.py CMD [ARG...]

Child stdout/stderr pass through untouched; after the child exits, one
JSON line `{"rss_wrap": {...}}` with peak child RSS (bytes) and wall
seconds is appended to THIS process's stderr, and the child's exit code
is propagated.
"""

import json
import resource
import subprocess
import sys
import time


def main() -> int:
    t0 = time.perf_counter()
    rc = subprocess.call(sys.argv[1:])
    wall = time.perf_counter() - t0
    ru = resource.getrusage(resource.RUSAGE_CHILDREN)
    # Linux reports ru_maxrss in KiB.
    print(
        json.dumps(
            {
                "rss_wrap": {
                    "argv": sys.argv[1:],
                    "rc": rc,
                    "wall_s": round(wall, 1),
                    "peak_rss_bytes": ru.ru_maxrss * 1024,
                    "peak_rss_gib": round(ru.ru_maxrss / 1048576, 2),
                }
            }
        ),
        file=sys.stderr,
        flush=True,
    )
    return rc


if __name__ == "__main__":
    sys.exit(main())
