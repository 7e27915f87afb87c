"""Loader for the native runtime extension with numpy fallbacks.

Mirrors the reference's lazy-import pattern for its C++ extensions (each
Python module imports its kernel lib and degrades to a Python path when
absent, e.g. apex/parallel/distributed.py:15-25 for apex_C.flatten).

``HAVE_NATIVE`` tells callers whether apex_tpu_C is loaded, and
``BUILD_ERROR`` why not when it should have been. All four entry points
below work identically either way:

    flatten(arrays, out)        -> bytes copied
    unflatten_into(flat, outs)  -> bytes copied
    assign_buckets(sizes, cap)  -> list[int] bucket ids (greedy, in order)
    pack_batch(samples, out)    -> batch size
"""

import os

import numpy as np


def _checkout_root():
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build_in_place():
    """Build csrc/apex_tpu_C.cpp into the source tree unless the binary
    there was built from exactly this source, then load it.

    The reference requires an explicit `pip install --cpp_ext` step; here
    the extension is one self-contained C++17 file, so a source checkout
    builds it on first import. A sidecar file keeps the sha256 of the
    source the binary was built from: a stale ``.so`` left in the tree
    (git-ignored, so copied along with it) is rebuilt, never loaded.
    Returns ``(module, None)`` or ``(None, reason)``."""
    import hashlib
    import importlib.util
    import shutil
    import subprocess
    import sys
    import sysconfig

    here = _checkout_root()
    src = os.path.join(here, "csrc", "apex_tpu_C.cpp")
    cxx = shutil.which("g++") or shutil.which("c++")
    if cxx is None:
        return None, "no C++ compiler (g++/c++) on PATH"
    so = os.path.join(
        here, "apex_tpu_C" + sysconfig.get_config_var("EXT_SUFFIX"))
    stamp = so + ".sha256"
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()

    def _fresh():
        if not (os.path.exists(so) and os.path.exists(stamp)):
            return False
        with open(stamp) as f:
            return f.read().strip() == digest

    def _load():
        spec = importlib.util.spec_from_file_location("apex_tpu_C", so)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules["apex_tpu_C"] = mod  # later imports reuse this instance
        return mod, None

    # Serialize concurrent importers (the multiproc launcher's workers all
    # import at once) behind an flock: one process compiles, the rest wait
    # and load the finished artifact. Compile lands in a temp path then an
    # atomic rename, so a crashed builder never leaves a truncated .so.
    import fcntl

    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        if _fresh():  # built before: no lock, no write to the tree
            return _load()
        with open(so + ".lock", "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            if _fresh():  # another process won the race
                return _load()
            cmd = [cxx, "-O3", "-std=c++17", "-shared", "-fPIC",
                   "-pthread", "-I" + sysconfig.get_path("include"),
                   src, "-o", tmp]
            proc = subprocess.run(cmd, capture_output=True, timeout=120)
            if proc.returncode != 0:
                return None, ("g++ failed:\n"
                              + proc.stderr.decode(errors="replace")[-2000:])
            os.replace(tmp, so)
            with open(stamp, "w") as f:
                f.write(digest)
            return _load()
    except (OSError, subprocess.TimeoutExpired, ImportError) as e:
        # read-only tree, compiler hang, unloadable artifact
        return None, repr(e)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _load_extension():
    """``(module, error)``: built from this checkout's csrc/ when the
    source is there (never a stale binary found on sys.path), else an
    installed ``apex_tpu_C`` (``pip install`` builds it via setup.py)."""
    if os.environ.get("APEX_TPU_NO_EXT", "").lower() not in (
            "", "0", "false", "no"):
        return None, None  # Python-only build, asked for
    if os.path.exists(os.path.join(_checkout_root(), "csrc",
                                   "apex_tpu_C.cpp")):
        return _build_in_place()
    try:
        import apex_tpu_C

        return apex_tpu_C, None
    except ImportError as e:
        return None, repr(e)


# BUILD_ERROR says why the numpy paths are in use when nobody asked for
# them (None otherwise); chip_smoke.py fails on it instead of letting a
# broken toolchain hide behind the fallback.
_ext, BUILD_ERROR = _load_extension()
HAVE_NATIVE = _ext is not None
if BUILD_ERROR is not None:
    import warnings

    warnings.warn("apex_tpu_C unavailable; using the numpy fallback: "
                  + BUILD_ERROR)


def _require_contiguous(a, what):
    """The native path rejects non-C-contiguous buffers via the buffer
    protocol; the fallback must match (reshape(-1) on a non-contiguous
    array would copy, silently dropping the writes)."""
    if not a.flags["C_CONTIGUOUS"]:
        raise ValueError(f"{what}: ndarray is not C-contiguous")
    return a


def flatten(arrays, out):
    if _ext is not None:
        return _ext.flatten(arrays, out)
    off = 0
    flat = _require_contiguous(out, "flatten").reshape(-1).view(np.uint8)
    total = sum(np.asarray(a).nbytes for a in arrays)
    if total > out.nbytes:
        raise ValueError(
            f"flatten: output buffer too small ({out.nbytes} < {total} bytes)")
    for a in arrays:
        b = np.ascontiguousarray(a).reshape(-1).view(np.uint8)
        flat[off:off + b.size] = b
        off += b.size
    return off


def unflatten_into(flat, outs):
    if _ext is not None:
        return _ext.unflatten_into(flat, outs)
    src = np.ascontiguousarray(flat).reshape(-1).view(np.uint8)
    total = sum(o.nbytes for o in outs)
    if total > flat.nbytes:
        raise ValueError(
            f"unflatten_into: flat buffer too small ({flat.nbytes} < "
            f"{total} bytes)")
    for o in outs:  # validate ALL before writing ANY (native acquires
        _require_contiguous(o, "unflatten_into")  # every buffer up front)
    off = 0
    for o in outs:
        n = o.nbytes
        o.reshape(-1).view(np.uint8)[:] = src[off:off + n]
        off += n
    return off


def assign_buckets(sizes, cap):
    if _ext is not None:
        return _ext.assign_buckets(list(sizes), int(cap))
    if cap <= 0:
        raise ValueError("assign_buckets: cap must be positive")
    out, acc, bucket, empty = [], 0, 0, True
    for sz in sizes:
        if not empty and acc + sz > cap:
            bucket += 1
            acc = 0
            empty = True
        acc += sz
        empty = False
        out.append(bucket)
    return out


def pack_batch(samples, out):
    if _ext is not None:
        return _ext.pack_batch(samples, out)
    if len(samples) == 0:
        raise ValueError("pack_batch: empty sample list")
    arrays = [np.asarray(s) for s in samples]
    item = arrays[0].nbytes
    if any(a.nbytes != item for a in arrays):
        raise ValueError("pack_batch: samples must be equally sized")
    if out.nbytes != item * len(arrays):
        raise ValueError(
            f"pack_batch: out must be batch*sample bytes ({out.nbytes} != "
            f"{len(arrays)}*{item})")
    batch = np.stack(arrays)
    _require_contiguous(out, "pack_batch")
    out.reshape(-1).view(np.uint8)[:] = batch.reshape(-1).view(np.uint8)
    return len(samples)
