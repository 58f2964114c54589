"""ctypes bindings for the native TFRecord reader (native/record_reader.cc).

Builds the shared library on first use (g++ is in the image; there is no
pybind11 — plain C ABI + ctypes per the environment's binding guidance) and
exposes two iterators:

  * ``iter_records(paths)``      — raw record payloads (bytes)
  * ``iter_batches_i32(...)``    — (batch, width) int32 arrays of a named
                                   Int64List feature, parsed in C++

Used by the MLM pipeline when ``DataConfig.use_native_reader`` is set; the
pure-tf.data path stays the default and the behavior contract (record
order, values) is identical — tested in tests/test_native_reader.py.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Iterator, Sequence

import numpy as np

log = logging.getLogger(__name__)

_SRC = os.path.join(os.path.dirname(__file__), "..", "native", "record_reader.cc")
_LIB_CACHE = os.path.join(os.path.dirname(__file__), "..", "native", "librecord_reader.so")
_lock = threading.Lock()
_lib = None


def _build() -> str:
    """Compile native/record_reader.cc into the (git-ignored) shared
    library next to it, unless a build of this exact source is already
    there."""
    lib = os.path.abspath(_LIB_CACHE)
    src = os.path.abspath(_SRC)
    # Validity = source CONTENT hash (sidecar file), not mtimes: a copy
    # of the tree gives lib and source the same mtime, so an mtime gate
    # would load a stale build after a source change.
    import hashlib

    with open(src, "rb") as f:
        src_hash = hashlib.sha256(f.read()).hexdigest()
    sidecar = lib + ".sha256"
    if os.path.exists(lib) and os.path.exists(sidecar):
        with open(sidecar) as f:
            if f.read().strip() == src_hash:
                return lib
    # Build under a per-process name and rename into place: several
    # processes (launcher-spawned workers) may reach first use together,
    # and none may dlopen a half-written file.
    tmp = f"{lib}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", "-pthread",
           src, "-ljpeg", "-o", tmp]
    log.info("building native record reader: %s", " ".join(cmd))
    try:
        subprocess.run(cmd, check=True, capture_output=True)
        os.replace(tmp, lib)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    with open(sidecar, "w") as f:
        f.write(src_hash + "\n")
    return lib


def load_library():
    global _lib
    with _lock:
        if _lib is None:
            lib = ctypes.CDLL(_build())
            lib.rr_open.restype = ctypes.c_void_p
            lib.rr_open.argtypes = [ctypes.POINTER(ctypes.c_char_p),
                                    ctypes.c_int, ctypes.c_int,
                                    ctypes.c_long, ctypes.c_uint64]
            lib.rr_skip.restype = ctypes.c_long
            lib.rr_skip.argtypes = [ctypes.c_void_p, ctypes.c_long]
            lib.rr_next_record.restype = ctypes.c_int
            lib.rr_next_record.argtypes = [
                ctypes.c_void_p, ctypes.POINTER(ctypes.POINTER(ctypes.c_char)),
                ctypes.POINTER(ctypes.c_long)]
            lib.rr_free.argtypes = [ctypes.POINTER(ctypes.c_char)]
            lib.rr_next_batch_i32.restype = ctypes.c_int
            lib.rr_next_batch_i32.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p,
                ctypes.POINTER(ctypes.c_int32), ctypes.c_int, ctypes.c_int]
            lib.rr_next_batch_images.restype = ctypes.c_int
            lib.rr_next_batch_images.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
                ctypes.POINTER(ctypes.c_float),
                ctypes.POINTER(ctypes.c_int32),
                ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_float),
                ctypes.POINTER(ctypes.c_float)]
            lib.rr_next_batch_images_eval.restype = ctypes.c_int
            lib.rr_next_batch_images_eval.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
                ctypes.POINTER(ctypes.c_float),
                ctypes.POINTER(ctypes.c_int32),
                ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
                ctypes.c_float,
                ctypes.POINTER(ctypes.c_float),
                ctypes.POINTER(ctypes.c_float)]
            lib.rr_error.restype = ctypes.c_char_p
            lib.rr_error.argtypes = [ctypes.c_void_p]
            lib.rr_close.argtypes = [ctypes.c_void_p]
            _lib = lib
    return _lib


# files-tuple → record count (same one-shot cache contract as
# tfdata.count_records, but through the C++ framing cursor — no TF
# dependency and no decode; restores rebuild pipelines so the count per
# shard set must not be repeated).
_COUNT_CACHE: dict[tuple[str, ...], int] = {}


def _norm_pointers(mean, std, null_f):
    """Per-channel (mean, std) → C float pointers, or nulls when neither
    is given. Exactly one of the pair is a caller bug — silently skipping
    normalization would feed unnormalized pixels downstream (ADVICE r3)."""
    if (mean is None) != (std is None):
        raise ValueError(
            "normalization needs BOTH mean and std (got only "
            + ("mean" if std is None else "std") + ")"
        )
    if mean is None:
        return None, None, null_f, null_f
    mean_arr = np.ascontiguousarray(mean, np.float32)
    std_arr = np.ascontiguousarray(std, np.float32)
    assert mean_arr.shape == (3,) and std_arr.shape == (3,)
    return (mean_arr, std_arr,
            mean_arr.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            std_arr.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))


def count_records_native(paths: Sequence[str]) -> int:
    key = tuple(paths)
    if key not in _COUNT_CACHE:
        reader = NativeRecordReader(key)
        try:
            _COUNT_CACHE[key] = reader.skip_records(2**62)
        finally:
            reader.close()
    return _COUNT_CACHE[key]


class NativeRecordReader:
    def __init__(self, paths: Sequence[str], prefetch: int = 256,
                 *, shuffle_window: int = 0, shuffle_seed: int = 0):
        """``shuffle_window > 1`` enables a windowed record-level shuffle
        (tf.data shuffle-buffer semantics) applied to every iterator of
        this handle, deterministic given ``shuffle_seed``. Memory cost is
        ``window`` raw records held in C++ (same class as tf.data's
        pre-decode shuffle buffer)."""
        self._lib = load_library()
        arr = (ctypes.c_char_p * len(paths))(
            *[p.encode() for p in paths]
        )
        self._h = self._lib.rr_open(arr, len(paths), prefetch,
                                    shuffle_window, shuffle_seed)
        if not self._h:
            raise RuntimeError("rr_open failed")

    def _check_error(self):
        err = self._lib.rr_error(self._h)
        if err:
            raise RuntimeError(f"native reader: {err.decode()}")

    def skip_records(self, n: int) -> int:
        """Advance the (possibly shuffled) stream ``n`` records without
        decode or C-ABI copies — the resume fast-skip. Returns how many
        were actually skipped (short on EOF)."""
        got = self._lib.rr_skip(self._h, n)
        if got < 0:
            self._check_error()
            raise RuntimeError("native reader skip failed")
        return int(got)

    def records(self) -> Iterator[bytes]:
        buf = ctypes.POINTER(ctypes.c_char)()
        n = ctypes.c_long()
        while True:
            rc = self._lib.rr_next_record(self._h, ctypes.byref(buf),
                                          ctypes.byref(n))
            if rc < 0:
                self._check_error()
                raise RuntimeError("native reader failed")
            if rc == 0:
                return
            try:
                yield ctypes.string_at(buf, n.value)
            finally:
                self._lib.rr_free(buf)

    def batches_i32(self, key: str, batch: int, width: int) -> Iterator[np.ndarray]:
        out = np.empty((batch, width), np.int32)
        ptr = out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
        while True:
            rc = self._lib.rr_next_batch_i32(self._h, key.encode(), ptr,
                                             batch, width)
            if rc < 0:
                self._check_error()
                raise RuntimeError(f"native reader parse error (rc={rc})")
            if rc == 0:
                return
            yield out.copy()

    def batches_images(self, batch: int, height: int, width: int,
                       *, image_key: str = "image/encoded",
                       label_key: str = "image/class/label",
                       threads: int = 0,
                       crop_seeds: Iterator[np.ndarray] | None = None,
                       mean: np.ndarray | None = None,
                       std: np.ndarray | None = None,
                       ) -> Iterator[tuple[np.ndarray, np.ndarray]]:
        """(images f32 (b,h,w,3) in [0,255], labels i32 (b,)) per batch.

        JPEG decode + bilinear resize run in C++ worker threads (the
        ImageNet host-side hot path, SURVEY.md §7 hard part 1); Python
        receives finished pixel batches. With ``crop_seeds`` (an iterator
        of (batch,) uint64 arrays, one per batch), each image gets an
        Inception-style distorted crop + random flip decoded via PARTIAL
        IDCT (libjpeg-turbo crop/skip-scanlines) — the decode cost tracks
        the crop area, the native twin of tf.data's decode_and_crop.
        ``mean``/``std`` (per-channel, length 3) fuse standardization into
        the native resize write, skipping a full numpy pass per batch.
        """
        images = np.empty((batch, height, width, 3), np.float32)
        labels = np.empty((batch,), np.int32)
        iptr = images.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
        lptr = labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
        null_seeds = ctypes.POINTER(ctypes.c_uint64)()
        null_f = ctypes.POINTER(ctypes.c_float)()
        # keep mean/std arrays referenced while their pointers are in use
        _mean_arr, _std_arr, mptr, sptr_std = _norm_pointers(mean, std, null_f)
        while True:
            if crop_seeds is not None:
                seeds = np.ascontiguousarray(next(crop_seeds), np.uint64)
                assert seeds.shape == (batch,)
                sptr = seeds.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))
            else:
                sptr = null_seeds
            rc = self._lib.rr_next_batch_images(
                self._h, image_key.encode(), label_key.encode(),
                iptr, lptr, batch, height, width, threads, sptr,
                mptr, sptr_std)
            if rc < 0:
                self._check_error()
                raise RuntimeError(f"native image decode error (rc={rc})")
            if rc == 0:
                return
            yield images.copy(), labels.copy()

    def batches_images_eval(self, batch: int, height: int, width: int,
                            *, image_key: str = "image/encoded",
                            label_key: str = "image/class/label",
                            threads: int = 0,
                            central_frac: float = 0.875,
                            mean: np.ndarray | None = None,
                            std: np.ndarray | None = None,
                            ) -> Iterator[tuple[np.ndarray, np.ndarray, int]]:
        """(images, labels, k) per batch for a SINGLE eval pass.

        Deterministic central-crop (``central_frac``, tf.image.central_crop
        arithmetic) + bilinear resize in C++ — the eval twin of
        ``batches_images``. ``k <= batch`` is the number of real records in
        the batch; the final batch is zero-padded past ``k`` (labels 0) so
        callers can weight the padding out (exact-eval contract)."""
        images = np.empty((batch, height, width, 3), np.float32)
        labels = np.empty((batch,), np.int32)
        iptr = images.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
        lptr = labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
        null_f = ctypes.POINTER(ctypes.c_float)()
        # keep mean/std arrays referenced while their pointers are in use
        _mean_arr, _std_arr, mptr, sptr_std = _norm_pointers(mean, std, null_f)
        while True:
            rc = self._lib.rr_next_batch_images_eval(
                self._h, image_key.encode(), label_key.encode(),
                iptr, lptr, batch, height, width, threads,
                central_frac, mptr, sptr_std)
            if rc < 0:
                self._check_error()
                raise RuntimeError(f"native eval decode error (rc={rc})")
            if rc == 0:
                return
            img = images.copy()
            lab = labels.copy()
            if rc < batch:  # zero the padded tail (weighted out by caller)
                img[rc:] = 0.0
                lab[rc:] = 0
            yield img, lab, rc

    def close(self):
        if self._h:
            self._lib.rr_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
