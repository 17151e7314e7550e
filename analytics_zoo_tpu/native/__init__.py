"""Native (C++) host-path acceleration.

The reference reaches native code for its data path and kernels over JNI
(SURVEY.md §2.3).  On TPU the device math belongs to XLA; the justified
native component is the *host* data path (SURVEY.md: "high-throughput
host-side decode/augment feeding infeed").  This package builds a small C++
library (ctypes-bound) providing:

- crc32c (TFRecord framing hot loop)
- uint8 image normalize/flip/crop batch kernels for the host feed

Build is lazy and optional: ``lib`` is None (pure-python fallbacks apply)
until :func:`build_native` succeeds; import never fails without a compiler.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess

logger = logging.getLogger("analytics_zoo_tpu")

_HERE = os.path.dirname(__file__)
_SO = os.path.join(_HERE, "libzoonative.so")
_SRC = os.path.join(_HERE, "zoonative.cpp")


class _NativeLib:
    def __init__(self, cdll):
        self._dll = cdll
        self._dll.zoo_crc32c.restype = ctypes.c_uint32
        self._dll.zoo_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
        self._dll.zoo_normalize_u8.restype = None
        self._dll.zoo_normalize_u8.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_float),
            ctypes.c_size_t, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
        ]
        self._dll.zoo_assemble_batch.restype = None
        self._dll.zoo_assemble_batch.argtypes = [
            ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32,
        ]
        self._dll.zoo_resize_bilinear_u8.restype = None
        self._dll.zoo_resize_bilinear_u8.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        ]

    def crc32c(self, data: bytes) -> int:
        return self._dll.zoo_crc32c(data, len(data))

    def normalize_u8(self, img, mean, std):
        """uint8 HWC image batch -> float32 normalized, in C."""
        import numpy as np

        img = np.ascontiguousarray(img, dtype=np.uint8)
        ch = img.shape[-1]
        out = np.empty(img.shape, dtype=np.float32)
        mean = np.ascontiguousarray(mean, dtype=np.float32)
        std = np.ascontiguousarray(std, dtype=np.float32)
        self._dll.zoo_normalize_u8(
            img.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            img.size, ch,
            mean.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            std.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        )
        return out

    def assemble_batch(self, images, offsets, flips, out_h, out_w,
                       n_threads=None):
        """Pack variable-size HWC uint8 images into one (N, oh, ow, C)
        uint8 batch with per-image crop offsets + horizontal flips, on C++
        threads.  ``offsets``/``flips`` come from the caller's seeded RNG
        so augmentation replay stays exact."""
        import numpy as np

        n = len(images)
        ch = images[0].shape[-1]
        imgs = [np.ascontiguousarray(im, dtype=np.uint8) for im in images]
        ptrs = (ctypes.c_void_p * n)(
            *[im.ctypes.data_as(ctypes.c_void_p).value for im in imgs])
        hw = np.ascontiguousarray(
            [[im.shape[0], im.shape[1]] for im in imgs], dtype=np.int32)
        off = np.ascontiguousarray(offsets, dtype=np.int32)
        flp = np.ascontiguousarray(flips, dtype=np.uint8)
        out = np.empty((n, out_h, out_w, ch), dtype=np.uint8)
        if n_threads is None:
            n_threads = min(8, os.cpu_count() or 1)
        self._dll.zoo_assemble_batch(
            ptrs,
            hw.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            off.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            flp.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            n, out_h, out_w, ch, int(n_threads),
        )
        return out

    def resize_bilinear(self, batch, out_h, out_w, n_threads=None):
        """(N, H, W, C) uint8 -> (N, oh, ow, C) uint8, half-pixel-center
        bilinear (cv2 INTER_LINEAR convention), on C++ threads."""
        import numpy as np

        batch = np.ascontiguousarray(batch, dtype=np.uint8)
        n, ih, iw, ch = batch.shape
        out = np.empty((n, out_h, out_w, ch), dtype=np.uint8)
        if n_threads is None:
            n_threads = min(8, os.cpu_count() or 1)
        self._dll.zoo_resize_bilinear_u8(
            batch.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            n, ih, iw, out_h, out_w, ch, int(n_threads),
        )
        return out


def build_native(force: bool = False):
    """Compile the C++ library with g++ (no external deps)."""
    global lib
    if os.path.exists(_SO) and not force:
        pass
    else:
        if not _compile(_SO):
            return None
    try:
        lib = _NativeLib(ctypes.CDLL(_SO))
        return lib
    except AttributeError:
        # a stale .so from an older source (missing a new symbol).  glibc
        # dlopen caches by path string IN-PROCESS, so rebuilding at the
        # same path cannot replace the already-loaded stale mapping:
        # compile to a UNIQUE path for this process's load, and install a
        # canonical copy at _SO for future imports.
        if force:
            logger.warning("native lib missing symbols even after rebuild")
            return None
        import tempfile

        uniq = os.path.join(tempfile.mkdtemp(prefix="zoonative-"),
                            "libzoonative.so")
        if not _compile(uniq):
            return None
        try:
            lib = _NativeLib(ctypes.CDLL(uniq))
        except (OSError, AttributeError) as e:
            logger.warning("native reload failed: %s", e)
            return None
        try:  # refresh the canonical .so so the NEXT process loads fresh
            import shutil

            shutil.copy(uniq, _SO + ".new")
            os.replace(_SO + ".new", _SO)
        except OSError:
            pass
        return lib
    except OSError as e:
        logger.warning("native load failed: %s", e)
        return None


def _compile(out_path: str) -> bool:
    # compile to a temp then rename: atomic for concurrent builders
    tmp = out_path + ".build"
    # no -march=native: the library is built from the committed source on
    # whichever host runs it, but a copied tree must not carry a binary
    # that only its build host's CPU can execute
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-pthread", "-o", tmp, _SRC]
    try:
        subprocess.run(cmd, check=True, capture_output=True)
        os.replace(tmp, out_path)
        return True
    except Exception as e:  # no compiler / failed build → fallback
        logger.warning("native build failed: %s", e)
        return False


lib = None
if os.path.exists(_SO):
    try:
        lib = _NativeLib(ctypes.CDLL(_SO))
    except (OSError, AttributeError):
        # unreadable or STALE .so (older source without a new symbol) —
        # keep the import-never-fails guarantee; build_native() rebuilds
        lib = None
