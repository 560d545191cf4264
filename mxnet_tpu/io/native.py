"""ctypes binding to the native IO library (src/cc/recordio.cc).

The reference's IO hot path is C++ (dmlc recordio + threaded iter);
this binds the TPU-native equivalent. The library is built on first use
with the repo Makefile (g++ is in the image; no pybind11 — plain C ABI
via ctypes, per the environment constraints).
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_LIB = None
_LOCK = threading.Lock()
_SRC_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "src", "cc")
_LIB_PATH = os.path.join(_SRC_DIR, "libmxtpu_io.so")
_STAMP_PATH = _LIB_PATH + ".srchash"
# what the library is built from — all tracked by git
_BUILD_INPUTS = ("Makefile", "recordio.cc", "image_batcher.cc")


class NativeIOUnavailable(RuntimeError):
    pass


def _source_hash():
    h = hashlib.sha256()
    for name in _BUILD_INPUTS:
        with open(os.path.join(_SRC_DIR, name), "rb") as f:
            h.update(name.encode() + b"\0" + f.read() + b"\0")
    return h.hexdigest()


def _build(want):
    """Build into a private name, then rename into place: a concurrent
    loader (xdist workers, engine processes) never maps a half-written
    library. The stamp is written last, so a build that died leaves no
    stamp and is redone."""
    tmp = f"libmxtpu_io.{os.getpid()}.so.tmp"
    try:
        subprocess.run(["make", "-B", "-C", _SRC_DIR, f"LIB={tmp}"],
                       check=True, capture_output=True)
    except (subprocess.CalledProcessError, FileNotFoundError) as e:
        raise NativeIOUnavailable(
            f"could not build native IO library: {e}") from e
    os.replace(os.path.join(_SRC_DIR, tmp), _LIB_PATH)
    with open(_STAMP_PATH + f".{os.getpid()}.tmp", "w") as f:
        f.write(want)
    os.replace(f.name, _STAMP_PATH)


def _load():
    global _LIB
    with _LOCK:
        if _LIB is not None:
            return _LIB
        # The .so is untracked: one found in the tree is trusted only
        # if its stamp names exactly the tracked sources it would be
        # built from now (file times say nothing after a copy or a
        # checkout).
        want = _source_hash()
        try:
            with open(_STAMP_PATH) as f:
                have = f.read()
        except FileNotFoundError:
            have = None
        if have != want or not os.path.exists(_LIB_PATH):
            _build(want)
        lib = ctypes.CDLL(_LIB_PATH)
        lib.mxio_reader_open.restype = ctypes.c_void_p
        lib.mxio_reader_open.argtypes = [ctypes.c_char_p]
        lib.mxio_reader_next.restype = ctypes.c_int64
        lib.mxio_reader_next.argtypes = [ctypes.c_void_p,
                                         ctypes.POINTER(ctypes.c_char_p)]
        lib.mxio_reader_close.argtypes = [ctypes.c_void_p]
        lib.mxio_batcher_create.restype = ctypes.c_void_p
        lib.mxio_batcher_create.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64, ctypes.c_int,
            ctypes.c_int, ctypes.c_uint64, ctypes.c_int64, ctypes.c_int64]
        lib.mxio_batcher_num_batches.restype = ctypes.c_int64
        lib.mxio_batcher_num_batches.argtypes = [ctypes.c_void_p]
        lib.mxio_batcher_next.restype = ctypes.c_int64
        lib.mxio_batcher_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_int64))]
        lib.mxio_batcher_free_batch.argtypes = [ctypes.c_void_p]
        lib.mxio_batcher_reset.argtypes = [ctypes.c_void_p]
        lib.mxio_batcher_close.argtypes = [ctypes.c_void_p]
        # image pipeline (decode+resize+batch on C++ threads)
        lib.mximg_batcher_create.restype = ctypes.c_void_p
        lib.mximg_batcher_create.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_uint64,
            ctypes.c_int64, ctypes.c_int64]
        lib.mximg_batcher_num_batches.restype = ctypes.c_int64
        lib.mximg_batcher_num_batches.argtypes = [ctypes.c_void_p]
        lib.mximg_batcher_next.restype = ctypes.c_int64
        lib.mximg_batcher_next.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
        lib.mximg_batcher_reset.argtypes = [ctypes.c_void_p]
        lib.mximg_batcher_close.argtypes = [ctypes.c_void_p]
        lib.mximg_decode.restype = ctypes.c_int
        lib.mximg_decode.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                     ctypes.c_int, ctypes.c_int,
                                     ctypes.c_void_p]
        _LIB = lib
        return lib


def available() -> bool:
    try:
        _load()
        return True
    except NativeIOUnavailable:
        return False


class NativeRecordReader:
    """Sequential reader over a RecordIO file (native framing)."""

    def __init__(self, path):
        self._lib = _load()
        self._h = self._lib.mxio_reader_open(path.encode())
        if not self._h:
            raise IOError(f"cannot open {path}")

    def read(self):
        buf = ctypes.c_char_p()
        n = self._lib.mxio_reader_next(self._h, ctypes.byref(buf))
        if n < 0:
            return None
        return ctypes.string_at(buf, n)

    def close(self):
        if self._h:
            self._lib.mxio_reader_close(self._h)
            self._h = None

    def __del__(self):
        self.close()


class NativeBatcher:
    """Threaded prefetching record batcher (iter_image_recordio_2 analog)."""

    def __init__(self, rec_path, idx_path=None, batch_size=32, num_threads=4,
                 shuffle=False, seed=0, num_parts=1, part_index=0):
        self._lib = _load()
        self._h = self._lib.mxio_batcher_create(
            rec_path.encode(), (idx_path or "").encode(), batch_size,
            num_threads, int(shuffle), seed, num_parts, part_index)
        if not self._h:
            raise IOError(f"cannot open {rec_path}")

    @property
    def num_batches(self):
        return self._lib.mxio_batcher_num_batches(self._h)

    def next(self):
        """Returns list[bytes] for one batch, or None at epoch end."""
        batch = ctypes.c_void_p()
        data = ctypes.c_char_p()
        offsets = ctypes.POINTER(ctypes.c_int64)()
        n = self._lib.mxio_batcher_next(self._h, ctypes.byref(batch),
                                        ctypes.byref(data),
                                        ctypes.byref(offsets))
        if n == 0:
            return None
        records = []
        base = ctypes.cast(data, ctypes.c_void_p).value
        for i in range(n):
            lo, hi = offsets[i], offsets[i + 1]
            records.append(ctypes.string_at(base + lo, hi - lo))
        self._lib.mxio_batcher_free_batch(batch)
        return records

    def reset(self):
        self._lib.mxio_batcher_reset(self._h)

    def close(self):
        if self._h:
            self._lib.mxio_batcher_close(self._h)
            self._h = None

    def __del__(self):
        self.close()


class NativeImageBatcher:
    """Full native image pipeline (src/cc/image_batcher.cc — the
    iter_image_recordio_2.cc equivalent): RecordIO framing, IRHeader
    parse, libjpeg decode, bilinear resize and CHW batch assembly on
    C++ threads. Each next() fills caller-owned numpy buffers — one
    contiguous uint8 (B,3,H,W) batch + float32 labels, ready for a
    single device_put. Partial final batches are discarded
    (last_batch='discard')."""

    def __init__(self, rec_path, idx_path, batch_size=32, data_shape=(3, 224, 224),
                 num_threads=4, shuffle=False, seed=0, num_parts=1,
                 part_index=0):
        import numpy as np
        self._np = np
        self._lib = _load()
        c, h, w = data_shape
        assert c == 3, "native image pipeline decodes RGB (3 channels)"
        self._shape = (batch_size, c, h, w)
        self._h = self._lib.mximg_batcher_create(
            rec_path.encode(), idx_path.encode(), batch_size, h, w,
            num_threads, int(shuffle), seed, num_parts, part_index)
        if not self._h:
            raise IOError(f"cannot open {rec_path} (or fewer records than "
                          "one batch)")

    @property
    def num_batches(self):
        return self._lib.mximg_batcher_num_batches(self._h)

    def next(self):
        """(data uint8 (n,3,H,W), labels float32 (n,)) or None at epoch
        end. n < batch_size when corrupt records were skipped (the
        native layer compacts the batch)."""
        np = self._np
        data = np.empty(self._shape, np.uint8)
        labels = np.empty(self._shape[0], np.float32)
        n = self._lib.mximg_batcher_next(
            self._h, data.ctypes.data_as(ctypes.c_void_p),
            labels.ctypes.data_as(ctypes.c_void_p))
        if n < 0:
            return None
        if n < self._shape[0]:
            import warnings
            warnings.warn(f"native image batcher: {self._shape[0] - n} "
                          "corrupt record(s) skipped in batch")
            return data[:n], labels[:n]
        return data, labels

    def reset(self):
        self._lib.mximg_batcher_reset(self._h)

    def close(self):
        if self._h:
            self._lib.mximg_batcher_close(self._h)
            self._h = None

    def __del__(self):
        self.close()


def decode_jpeg(buf, out_h, out_w):
    """Native single-image decode+resize → uint8 (3, out_h, out_w)."""
    import numpy as np
    lib = _load()
    out = np.empty((3, out_h, out_w), np.uint8)
    rc = lib.mximg_decode(buf, len(buf), out_h, out_w,
                          out.ctypes.data_as(ctypes.c_void_p))
    if rc != 0:
        raise ValueError("corrupt JPEG")
    return out
