"""Build the native interning table (g++ → shared object).

No pybind11/cffi-compile step: plain C ABI + ctypes.  The .so is built
on demand next to the source and cached by source hash, so a fresh
checkout self-builds on first use (~1s) and rebuilds only when the
source changes.  Set GUBERNATOR_TPU_NATIVE=0 to skip native entirely.

Sanitizer mode (guberlint's native runtime companion —
STATIC_ANALYSIS.md): GUBER_NATIVE_SAN=thread|address (or =1 for
thread) compiles with -fsanitize and a separate cache tag.  A
sanitizer runtime cannot initialize when dlopen'd into an
uninstrumented python, so instrumented .so's are meant for SUBPROCESS
tests that LD_PRELOAD the runtime (see sanitizer_preload() and
tests/test_h2_server_san.py), not for in-process serving.
"""

from __future__ import annotations

import hashlib
import logging
import os
import subprocess
from pathlib import Path
from typing import Optional

log = logging.getLogger("gubernator_tpu.native")

_NATIVE_DIR = Path(__file__).parent / "native"
_BUILD_DIR = _NATIVE_DIR / "build"


def san_mode() -> str:
    """'' (off), 'thread', or 'address' — from GUBER_NATIVE_SAN."""
    v = os.environ.get("GUBER_NATIVE_SAN", "").strip().lower()
    if v in ("", "0", "off", "none"):
        return ""
    if v in ("1", "thread", "tsan"):
        return "thread"
    if v in ("address", "asan"):
        return "address"
    log.warning("GUBER_NATIVE_SAN=%r not recognized; sanitizer off", v)
    return ""


def sanitizer_preload(mode: Optional[str] = None) -> Optional[str]:
    """Path to the sanitizer runtime to LD_PRELOAD into a subprocess
    running an instrumented .so, or None when unavailable."""
    mode = san_mode() if mode is None else mode
    if not mode:
        return None
    lib = {"thread": "libtsan.so", "address": "libasan.so"}[mode]
    try:
        out = subprocess.run(
            ["g++", f"-print-file-name={lib}"],
            capture_output=True, text=True, timeout=10, check=True,
        ).stdout.strip()
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired, OSError):
        return None
    return out if out and os.path.sep in out and Path(out).exists() else None


# Stems built from more than one translation unit.  The h2 front links
# the decision plane (GIL-free hot-key serve inside the connection
# threads) and the wire codec (its body decode / response encode) into
# one .so, so dp_try_serve is an ordinary in-image call for the server.
_EXTRA_SOURCES = {
    "h2_server": [
        "decision_plane.cpp", "wire_codec.cpp", "event_ring.cpp",
        "columnar_feeder.cpp",
    ],
}


# What serves instead when a library cannot be built — said in the
# warning, because the Python tier answers correctly and nothing else
# would tell an operator the native one is gone.
_TIER_LOST = {
    "intern_table": "the C++ intern table and its batch schedule(); the "
    "Python InternTable (a per-key dict walk) serves",
    "wire_codec": "the native wire decode/encode; every RPC takes the "
    "protobuf + dataclass path",
    "h2_server": "the native h2 front, decision plane and columnar "
    "feeder (GUBER_H2_FAST_ADDRESS cannot be served)",
    "h2_client": "the native h2 load client (bench/herd modes only)",
    "hotkeys": "the native hot-key table; the Python one serves, a "
    "per-key loop under the interpreter lock on every RPC's thread",
}


def ensure_built(stem: str = "intern_table") -> Optional[Path]:
    """Compile `native/<stem>.cpp` (plus any _EXTRA_SOURCES companions)
    if needed; returns the .so path or None on failure."""
    if os.environ.get("GUBERNATOR_TPU_NATIVE", "1") == "0":
        return None
    san = san_mode()
    src = _NATIVE_DIR / f"{stem}.cpp"
    sources = [src] + [
        _NATIVE_DIR / extra for extra in _EXTRA_SOURCES.get(stem, [])
    ]
    digest = hashlib.sha256()
    for s in sources:
        digest.update(s.read_bytes())
    tag = digest.hexdigest()[:16]
    if san:
        tag = f"{tag}-{san[0]}san"
    so = _BUILD_DIR / f"{stem}-{tag}.so"
    if so.exists():
        return so
    _BUILD_DIR.mkdir(parents=True, exist_ok=True)
    tmp = so.with_suffix(".so.tmp")
    # No -march=native: the .so is cached on disk and a copy built on a
    # newer CPU would SIGILL elsewhere (ctypes can't catch signals).
    cmd = [
        "g++",
        # Sanitized builds keep frames/symbols and dial optimization
        # back so TSan/ASan reports carry usable stacks.
        "-O1" if san else "-O3",
        "-std=c++17",
        "-shared",
        "-fPIC",
        "-pthread",
    ]
    if san:
        cmd += [f"-fsanitize={san}", "-g", "-fno-omit-frame-pointer"]
    cmd += ["-o", str(tmp)] + [str(s) for s in sources]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired, OSError) as e:
        detail = getattr(e, "stderr", b"")
        log.warning(
            "native %s build failed — LOST: %s: %s %s",
            stem,
            _TIER_LOST.get(stem, "this native tier"),
            e,
            detail.decode(errors="replace") if detail else "",
        )
        return None
    os.replace(tmp, so)
    # Drop stale builds of older source versions — within the same
    # variant only (a plain build must not evict a sanitized .so, nor
    # tsan an asan one, and vice versa).
    suffix = f"-{san[0]}san.so" if san else ".so"
    for old in _BUILD_DIR.glob(f"{stem}-*.so"):
        if old == so:
            continue
        if san:
            stale = old.name.endswith(suffix)
        else:
            stale = not old.name.endswith(("-tsan.so", "-asan.so"))
        if stale:
            old.unlink(missing_ok=True)
    return so
