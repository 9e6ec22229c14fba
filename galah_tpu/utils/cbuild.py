"""Shared ctypes builder/loader for the native C kernels.

One implementation of the compile-on-first-import idiom used by
io/_cingest.py, ops/_cpairstats.py, and ops/_csketch.py: each library
is named by a hash of its source, compiler and flags, so a library
built from other source (one copied in with a checkout, say) is never
loaded; pid-suffixed temp + atomic os.replace (concurrent importers
never dlopen a half-written library); and a process-wide failure cache
so a broken toolchain or read-only package dir raises ImportError
instantly on every retry instead of re-spawning the compiler per call
(the caller modules are evicted from sys.modules when their import
fails, so without this cache each fallback call would re-run cc).
Every failure mode — including a corrupt/incompatible existing library
(dlopen OSError) — surfaces as ImportError, the contract the callers'
JAX/numpy fallbacks catch.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import pathlib
import subprocess
import sysconfig

_CSRC = pathlib.Path(__file__).resolve().parent.parent.parent / "csrc"
_SOSUFFIX = sysconfig.get_config_var("EXT_SUFFIX") or ".so"

_FAILED: dict[str, str] = {}


def build_key(source: bytes, cc: str, extra_flags: tuple) -> str:
    """The name a library built from `source` with `cc` and
    `extra_flags` carries: any change to one of them names a new
    library."""
    h = hashlib.sha256(source)
    for part in (cc, *extra_flags):
        h.update(b"\0" + part.encode())
    return h.hexdigest()[:16]


def build_and_load(src_name: str, lib_stem: str, out_dir,
                   extra_flags: tuple = (),
                   disable_env: str | None = None) -> ctypes.CDLL:
    """Compile csrc/<src_name> into <out_dir>/<lib_stem>-<hash><EXT_SUFFIX>
    (unless that library exists) and dlopen it. Raises ImportError on
    any failure — cached, so repeated attempts are cheap."""
    if disable_env and os.environ.get(disable_env):
        raise ImportError(f"native kernel disabled via {disable_env}")
    if src_name in _FAILED:
        raise ImportError(_FAILED[src_name])
    try:
        src = _CSRC / src_name
        if not src.is_file():
            raise ImportError(f"native source missing: {src}")
        cc = os.environ.get("CC", "cc")
        lib = pathlib.Path(out_dir) / (
            f"{lib_stem}-{build_key(src.read_bytes(), cc, extra_flags)}"
            f"{_SOSUFFIX}")
        if not lib.is_file():
            tmp = lib.with_name(f"{lib.stem}.{os.getpid()}{lib.suffix}")
            cmd = [cc, "-O3", "-shared", "-fPIC", "-o", str(tmp),
                   str(src), *extra_flags]
            try:
                proc = subprocess.run(cmd, capture_output=True,
                                      text=True, timeout=120)
                if proc.returncode != 0:
                    raise ImportError(
                        f"native build failed: {' '.join(cmd)}\n"
                        f"{proc.stderr}")
                os.replace(tmp, lib)
            except (OSError, subprocess.TimeoutExpired) as e:
                raise ImportError(f"native build failed to run: {e}")
            finally:
                tmp.unlink(missing_ok=True)
        try:
            return ctypes.CDLL(str(lib))
        except OSError as e:
            raise ImportError(f"native library load failed ({lib}): {e}")
    except ImportError as e:
        _FAILED[src_name] = str(e)
        raise
