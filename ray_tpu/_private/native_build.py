"""Compile-and-cache for the native (C++) runtime components.

One build path for every src/*.cc library (shm arena, futex channels):
the output name embeds a content hash of the source, so a source change
rebuilds automatically regardless of file timestamps, and a stale or
foreign binary is never loaded (git does not preserve mtimes — see the
round-1 advisory on the committed .so).
"""
from __future__ import annotations

import hashlib
import os
import subprocess
import threading
from typing import Sequence

_lock = threading.Lock()


def build_native_library(src_path: str, prefix: str,
                         extra_flags: Sequence[str] = (), force: bool = False) -> str:
    """Build `src_path` into lib<prefix>.<hash>.so next to the source
    (cached by content hash); returns the library path."""
    with open(src_path, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    lib = os.path.join(os.path.dirname(src_path), f"lib{prefix}.{digest}.so")
    with _lock:
        if force or not os.path.exists(lib):
            tmp = lib + f".tmp.{os.getpid()}"
            cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", "-o", tmp, src_path,
                   *extra_flags]
            # the .so is not in git: a checkout builds it on first use, so
            # a missing or failing compiler has to say so in full
            try:
                proc = subprocess.run(cmd, capture_output=True, text=True)
            except FileNotFoundError as e:
                raise RuntimeError(
                    f"ray_tpu builds {os.path.basename(src_path)} with g++ on first use, "
                    "and no g++ is on PATH") from e
            if proc.returncode != 0:
                raise RuntimeError(f"{' '.join(cmd)} failed:\n{proc.stderr}")
            # atomic: raylet, driver and workers may all build at once on
            # a fresh checkout; each renames its own complete file
            os.replace(tmp, lib)
            # drop builds of older source revisions
            d = os.path.dirname(lib)
            for name in os.listdir(d):
                if (
                    name.startswith(f"lib{prefix}.")
                    and name.endswith(".so")
                    and os.path.join(d, name) != lib
                ):
                    try:
                        os.unlink(os.path.join(d, name))
                    except OSError:
                        pass
    return lib
