"""Native runtime components, built from source on first use.

The shared objects are not committed: `build(lib)` runs this directory's
Makefile when `lib` is missing or older than its source, holding a file
lock so concurrent importers (pytest-xdist workers, a node and a tool on
one checkout) build it once.  `g++` and `make` are required.
"""

from __future__ import annotations

import fcntl
import os
import subprocess
from typing import Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_SOURCES = {
    "libsha256_batch.so": "sha256_batch.cpp",
    "libkvstore.so": "kvstore.cpp",
    "libsnappy_tpu.so": "snappy.cpp",
}


def _stale(lib: str) -> bool:
    path = os.path.join(_DIR, lib)
    src = os.path.join(_DIR, _SOURCES[lib])
    return not os.path.exists(path) or (
        os.path.getmtime(path) < os.path.getmtime(src)
    )


def build(lib: str) -> Optional[str]:
    """Path of the built `lib`, building it first when needed; None
    when the build fails (the caller keeps its pure-Python fallback)."""
    with open(os.path.join(_DIR, ".build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if _stale(lib):
            try:
                proc = subprocess.run(
                    ["make", "-C", _DIR, lib],
                    capture_output=True,
                    text=True,
                    timeout=300,
                )
            except (OSError, subprocess.TimeoutExpired) as e:
                _warn(lib, str(e))
                return None
            if proc.returncode != 0:
                _warn(lib, proc.stderr.strip()[-500:])
                return None
    return os.path.join(_DIR, lib)


def _warn(lib: str, detail: str) -> None:
    from ..utils.logger import get_logger

    get_logger("native").warn(f"building {lib} failed: {detail}")
