"""Thumbnailer: WebP previews in a cas_id-sharded cache.

Mirrors core/src/object/media/thumbnail/ — target area 262,144 px² at WebP
quality 30 (mod.rs:95-110), cache layout ``thumbnails/<shard>/<cas_id>.webp``
where the shard is the first 2 hex chars of the cas_id (shard.rs:8), and a
versioned thumbnails directory (directory.rs).

Image decode prefers the native C++ helpers (sd_images.cc: libjpeg/libpng/
libwebp) with a PIL fallback; video frame extraction links FFmpeg the way
the reference's sd-ffmpeg crate does (sd_ffmpeg.cc over libavformat/
libavcodec/libswscale, preferring embedded cover art then seeking 10% in —
crates/ffmpeg/src/thumbnailer.rs), with an ffmpeg-CLI fallback.
"""

from __future__ import annotations

import logging
import math
import shutil
import threading
import subprocess
from pathlib import Path

from ... import faults
from ...recovery import is_disk_full, note_disk_full
from ...utils.atomic import atomic_path, atomic_write_bytes, atomic_write_text

logger = logging.getLogger(__name__)

TARGET_PX = 262_144.0
WEBP_QUALITY = 30
THUMBNAIL_VERSION = 1

THUMBNAILABLE_IMAGE_EXTENSIONS = {
    "jpg", "jpeg", "png", "gif", "bmp", "webp", "tiff", "tif", "ico",
}
THUMBNAILABLE_VIDEO_EXTENSIONS = {
    "mp4", "mkv", "avi", "mov", "webm", "m4v", "mpg", "mpeg",
}
#: decoded via dlopen'd libheif (sd_heif.cc) when the runtime is present
HEIF_EXTENSIONS = {"heic", "heif", "avif"}

_FFMPEG = shutil.which("ffmpeg")


_THUMB_DIRS_READY: set[str] = set()


def thumbnail_dir(data_dir: str | Path) -> Path:
    d = Path(data_dir) / "thumbnails"
    # mkdir/version-stamp once per data_dir per process: this runs on hot
    # listing paths (one call per thumbnail_path)
    key = str(d)
    if key not in _THUMB_DIRS_READY:
        d.mkdir(parents=True, exist_ok=True)
        version_file = d / "version.txt"
        if not version_file.exists():
            atomic_write_text(version_file, str(THUMBNAIL_VERSION))
        # benign race: mkdir/version-stamp are idempotent and the set is a
        # pure memo — double work on a concurrent first call, never
        # corruption, and the hot listing path stays lock-free
        _THUMB_DIRS_READY.add(key)  # lint: ok(lock-discipline)
    return d


def thumbnail_path(data_dir: str | Path, cas_id: str) -> Path:
    """cas_id-sharded cache path (shard.rs: first two hex chars)."""
    return thumbnail_dir(data_dir) / cas_id[:2] / f"{cas_id}.webp"


def can_generate_thumbnail(extension: str | None) -> bool:
    ext = (extension or "").lower()
    return ext in THUMBNAILABLE_IMAGE_EXTENSIONS or (
        ext in THUMBNAILABLE_VIDEO_EXTENSIONS and _ffmpeg_capable()
    ) or (ext in HEIF_EXTENSIONS and _native_heif() is not None)


def _ffmpeg_capable() -> bool:
    """Can SOME backend decode video here? Answered without compiling:
    this runs on listing paths, where a synchronous g++ attempt (seconds,
    repeated each process on hosts where the build fails) is not
    acceptable. The real build happens on first generation, inside a job."""
    if _FFMPEG is not None:
        return True
    if _NATIVE_FFMPEG is not None:  # probe already ran: trust its answer
        return _NATIVE_FFMPEG[0] is not None
    import glob

    return bool(glob.glob("/usr/include/libavformat")
                or glob.glob("/usr/include/*/libavformat")
                or glob.glob("/usr/local/include/libavformat"))


def generate_thumbnail(source: str | Path, data_dir: str | Path, cas_id: str,
                       extension: str | None = None) -> Path | None:
    """Create (or reuse) the WebP thumbnail for one file; returns the path.

    Skip-and-log on ANY failure (including ENOSPC — the ``thumbnail``
    chaos seam rehearses it): a thumbnail is regenerable, so a full disk
    degrades to "no preview yet", never a failed media job. Writes are
    atomic (utils/atomic), so a kill mid-encode leaves no torn WebP for
    the explorer to render."""
    out = thumbnail_path(data_dir, cas_id)
    if out.exists():
        return out
    out.parent.mkdir(parents=True, exist_ok=True)
    ext = (extension or Path(source).suffix.lstrip(".")).lower()
    try:
        faults.inject("thumbnail", key=cas_id)
        if ext in THUMBNAILABLE_VIDEO_EXTENSIONS:
            return _video_thumbnail(Path(source), out)
        return _image_thumbnail(Path(source), out, ext)
    except Exception as e:
        if is_disk_full(e):
            note_disk_full("thumbnail")
        logger.warning("thumbnail failed for %s: %s", source, e)
        return None


_NATIVE_IMAGES: list | None = None  # [module_or_None] once probed
_NATIVE_FFMPEG: list | None = None
_NATIVE_HEIF: list | None = None


def _native_heif():
    """libheif-backed decode (sd-images `heif` feature) if the runtime
    loads; probe cached like the other native helpers."""
    global _NATIVE_HEIF
    if _NATIVE_HEIF is None:
        try:
            from ...native import heif_native

            _NATIVE_HEIF = [heif_native if heif_native.available() else None]
        except Exception as e:
            logger.info("heif support unavailable (%s)", e)
            _NATIVE_HEIF = [None]
    return _NATIVE_HEIF[0]


def _native_ffmpeg():
    """Linked FFmpeg decoder (sd_ffmpeg.cc) if buildable; probe cached like
    the image helper — a failed import involves a g++ attempt."""
    global _NATIVE_FFMPEG
    if _NATIVE_FFMPEG is None:
        try:
            from ...native import ffmpeg_native

            _NATIVE_FFMPEG = [ffmpeg_native]
        except Exception as e:
            logger.info("native ffmpeg unavailable (%s); using CLI if present", e)
            _NATIVE_FFMPEG = [None]
    return _NATIVE_FFMPEG[0]


def _native_images():
    """sd-images equivalent (C++ libjpeg/libpng/libwebp) if buildable.
    The probe result is cached — a failed import involves a g++ attempt and
    must not re-run per image."""
    global _NATIVE_IMAGES
    if _NATIVE_IMAGES is None:
        try:
            from ...native import images_native

            _NATIVE_IMAGES = [images_native]
        except Exception as e:
            logger.info("native image helper unavailable (%s); using PIL", e)
            _NATIVE_IMAGES = [None]
    return _NATIVE_IMAGES[0]


def _native_decode(source: Path, max_edge: int):
    """numpy RGB via the native decoder, or None → caller uses PIL."""
    native = _native_images()
    ext = source.suffix.lstrip(".").lower()
    if native is None or ext not in native.NATIVE_DECODE_EXTENSIONS:
        return None
    try:
        return native.decode_rgb(source, max_edge=max_edge)
    except Exception as e:
        logger.debug("native decode fell back to PIL for %s: %s", source, e)
        return None


def _image_thumbnail(source: Path, out: Path, ext: str | None = None) -> Path:
    from PIL import Image

    if (ext or source.suffix.lstrip(".").lower()) in HEIF_EXTENSIONS:
        heif = _native_heif()
        if heif is None:
            raise RuntimeError("libheif runtime not available")
        arr = heif.decode_rgb(source)
    else:
        # native decode (JPEG prescaled in DCT space near the target)
        arr = _native_decode(source, MAX_INPUT_EDGE)
    img = Image.fromarray(arr) if arr is not None else Image.open(source)
    with img:
        img = img.convert("RGB") if img.mode not in ("RGB", "RGBA") else img
        w, h = img.size
        # scale so w*h ≈ TARGET_PX (thumbnail/mod.rs:95-100 sqrt scale factor)
        if w * h > TARGET_PX:
            factor = math.sqrt(TARGET_PX / (w * h))
            img = img.resize((max(1, round(w * factor)), max(1, round(h * factor))))
        with atomic_path(out) as tmp:
            _save_webp(img, tmp)
    return out


def _save_webp(img, tmp: Path) -> None:
    native = _native_images()
    if native is not None:
        try:
            import numpy as np

            rgb = np.asarray(img.convert("RGB"), dtype=np.uint8)
            tmp.write_bytes(native.encode_webp(rgb, WEBP_QUALITY))
            return
        except Exception as e:
            logger.debug("native webp encode fell back to PIL: %s", e)
    img.save(tmp, "WEBP", quality=WEBP_QUALITY)


def _video_thumbnail(source: Path, out: Path) -> Path | None:
    native = _native_ffmpeg()
    if native is not None:
        try:
            from PIL import Image

            # one representative frame (cover art preferred, else 10% in),
            # then the same √(area) scale + WebP path images take
            frame = native.decode_frame_rgb(source)
            img = Image.fromarray(frame)
            w, h = img.size
            if w * h > TARGET_PX:
                factor = math.sqrt(TARGET_PX / (w * h))
                img = img.resize((max(1, round(w * factor)),
                                  max(1, round(h * factor))))
            with atomic_path(out) as tmp:
                _save_webp(img, tmp)
            return out
        except Exception as e:
            logger.debug("native video decode failed for %s (%s); CLI fallback",
                         source, e)
    if _FFMPEG is None:
        return None
    with atomic_path(out) as tmp:
        _cli_grab_frame(source, tmp, 512, webp_quality=WEBP_QUALITY)
    return out


def _cli_grab_frame(source: Path, out: Path, size: int,
                    webp_quality: int | None = None) -> None:
    """One frame via the ffmpeg CLI — the single place the grab command
    lives (seek heuristic, scale filter, timeout) so the thumbnail and
    bytes-helper paths can't drift apart."""
    cmd = [_FFMPEG, "-y", "-loglevel", "error", "-ss", "00:00:01",
           "-i", str(source), "-frames:v", "1",
           "-vf", f"scale='min({size},iw)':-2"]
    if webp_quality is not None:
        # explicit container: the atomic-write temp has no .webp suffix for
        # ffmpeg to infer the format from
        cmd += ["-f", "webp", "-quality", str(webp_quality)]
    subprocess.run(cmd + [str(out)], check=True, timeout=30,
                   capture_output=True)


# ---------------------------------------------------------------------------
# video helper surface (crates/ffmpeg/src/lib.rs:19-47 to_thumbnail /
# to_webp_bytes, film_strip.rs filter)
# ---------------------------------------------------------------------------


def film_strip_filter(arr):
    """Overlay sprocket-hole strips down both edges — the film_strip.rs
    effect, drawn procedurally (dark band, repeating light holes) instead
    of from baked pattern tiles."""
    import numpy as np

    arr = np.asarray(arr, dtype=np.uint8)
    h, w = arr.shape[:2]
    strip_w = max(4, w // 16)
    hole_h = max(2, strip_w // 2)
    period = hole_h * 2
    out = arr.copy()
    hole_w = max(1, strip_w // 2)
    x_off = (strip_w - hole_w) // 2
    for x0 in (0, w - strip_w):
        strip = out[:, x0:x0 + strip_w]
        strip[:] = (strip * 0.15).astype(np.uint8)
        for y0 in range(period // 2, h - hole_h, period):
            strip[y0:y0 + hole_h, x_off:x_off + hole_w] = 230
    return out


def video_to_webp_bytes(source: str | Path, size: int = 256,
                        quality: int = WEBP_QUALITY,
                        film_strip: bool = False) -> bytes:
    """One WebP-encoded video thumbnail as bytes (lib.rs to_webp_bytes;
    the builder's film_strip flag is opt-in here, like core's usage).
    Uses the linked decoder when it builds, else the ffmpeg CLI —
    the same capability set as generate_thumbnail's video path."""
    import io

    import numpy as np
    from PIL import Image

    frame = None
    native = _native_ffmpeg()
    if native is not None:
        try:
            frame = native.decode_frame_rgb(Path(source), target_edge=size)
        except Exception as e:
            logger.debug("native video decode failed for %s (%s); "
                         "CLI fallback", source, e)
    if frame is None:
        if _FFMPEG is None:
            raise RuntimeError("no video decode backend (libav libs or "
                               "ffmpeg CLI required)")
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            tmp = Path(td) / "frame.png"
            _cli_grab_frame(Path(source), tmp, size)
            with Image.open(tmp) as img:
                frame = np.asarray(img.convert("RGB"), dtype=np.uint8)
    if film_strip:
        frame = film_strip_filter(frame)
    native = _native_images()
    if native is not None:
        try:
            return native.encode_webp(frame, quality)
        except Exception:
            # PIL below produces the same artifact; log the fallback or a
            # broken native encoder silently halves encode throughput
            logger.debug("native webp encode failed; using PIL",
                         exc_info=True)
    buf = io.BytesIO()
    Image.fromarray(frame).save(buf, "WEBP", quality=quality)
    return buf.getvalue()


def video_to_thumbnail(source: str | Path, out: str | Path, size: int = 256,
                       quality: int = WEBP_QUALITY,
                       film_strip: bool = False) -> None:
    """Write a video thumbnail file (lib.rs to_thumbnail)."""
    out = Path(out)
    atomic_write_bytes(out, video_to_webp_bytes(source, size, quality,
                                                film_strip))


# ---------------------------------------------------------------------------
# batched device path (ops/resize_jax.py)
# ---------------------------------------------------------------------------

#: host box-reduce target: ≤2× the 512px output canvas, so the device's
#: 4-tap bilinear never skips source pixels (no aliasing) and transfers
#: stay 4× smaller than a 2048-edge canvas
MAX_INPUT_EDGE = 1024


def _decode_for_device(source: Path):
    """Decode (native libjpeg/libpng when available, JPEG prescaled in DCT
    space) + integer box-reduce to ≤MAX_INPUT_EDGE — cheap antialias
    pre-pass; the device kernel does the fractional bilinear step."""
    import numpy as np
    from PIL import Image

    if source.suffix.lstrip(".").lower() in HEIF_EXTENSIONS:
        heif = _native_heif()
        if heif is None:
            raise RuntimeError("libheif runtime not available")
        arr = heif.decode_rgb(source)
    else:
        arr = _native_decode(source, MAX_INPUT_EDGE)
    if arr is not None:
        edge = max(arr.shape[0], arr.shape[1])
        if edge > MAX_INPUT_EDGE:  # PNG has no in-decode scaling
            k = -(-edge // MAX_INPUT_EDGE)
            h, w = (arr.shape[0] // k) * k, (arr.shape[1] // k) * k
            arr = arr[:h, :w].reshape(h // k, k, w // k, k, 3) \
                .mean(axis=(1, 3)).astype(np.uint8)
        return arr
    with Image.open(source) as img:
        img = img.convert("RGB")
        edge = max(img.size)
        if edge > MAX_INPUT_EDGE:
            img = img.reduce(-(-edge // MAX_INPUT_EDGE))
        return np.asarray(img, dtype=np.uint8)


#: sticky per-process verdict on the device resize path: None = unmeasured,
#: True = device wins, False = device loses (every later batch goes scalar).
#: Even with the tpuThumbnails feature ON, the processor must never keep a
#: measurably losing path: where the per-image transfer alone exceeds the
#: whole scalar pipeline the verdict goes scalar, and where the device
#: wins it keeps batching.
#: The lock keeps concurrent first batches from probing simultaneously
#: (interleaved device calls would distort both measurements).
_DEVICE_VERDICT: dict = {"value": None}
_VERDICT_LOCK = threading.Lock()
#: batches smaller than this never decide the verdict — a 1–2 image call
#: charges the whole dispatch overhead to one image and would latch the
#: scalar path on hosts where normal batches win
_VERDICT_MIN_BATCH = 4


def _measure_device_verdict(batch_arrays, dt_device: float) -> bool:
    """Compare the (warm) device per-image resize time against PIL doing
    the same resize step on the same decoded arrays."""
    import time as _time

    import numpy as np
    from PIL import Image

    from ...ops.resize_jax import target_dims

    sample = batch_arrays[: min(8, len(batch_arrays))]
    t0 = _time.perf_counter()
    for arr in sample:
        th, tw = target_dims(arr.shape[1], arr.shape[0])
        np.asarray(Image.fromarray(arr).resize((tw, th), Image.BILINEAR))
    scalar_per_img = (_time.perf_counter() - t0) / len(sample)
    device_per_img = dt_device / len(batch_arrays)
    verdict = device_per_img <= scalar_per_img
    logger.info("thumbnail device verdict: device %.1f ms/img vs scalar "
                "%.1f ms/img — %s", device_per_img * 1e3, scalar_per_img * 1e3,
                "keeping device batching" if verdict
                else "routing to scalar for the rest of this process")
    return verdict


def _scalar_all(entries, data_dir: str | Path) -> dict:
    """Scalar pipeline over [(source, cas_id, ext)]; the shared fallback of
    every losing/failed device route."""
    out_paths: dict = {}
    for source, cas_id, ext in entries:
        made = generate_thumbnail(source, data_dir, cas_id, ext)
        if made is not None:
            out_paths[cas_id] = made
    return out_paths


def _device_resize_allowed() -> bool:
    """get_hasher-style routing gate for the batched resize: the sticky
    verdict when measured, else False outright on hosts with no accelerator
    platform — a jnp resize on XLA:CPU loses to PIL by an order of
    magnitude, so the fallback must never even decode
    for the device."""
    if _DEVICE_VERDICT["value"] is not None:
        return _DEVICE_VERDICT["value"]
    from ...objects.hasher import _accelerator_available

    if not _accelerator_available():
        with _VERDICT_LOCK:
            if _DEVICE_VERDICT["value"] is None:
                logger.info("thumbnail routing: no accelerator platform — "
                            "scalar PIL path for this process")
                _DEVICE_VERDICT["value"] = False
        return _DEVICE_VERDICT["value"]
    return True  # real accelerator, unmeasured: let the warm batch decide


def _pil_resize_all(arrays) -> list:
    """Scalar resize of decoded RGB arrays, dimension-identical to the
    device kernel (same target_dims math)."""
    import numpy as np
    from PIL import Image

    from ...ops.resize_jax import target_dims

    out = []
    for arr in arrays:
        th, tw = target_dims(arr.shape[1], arr.shape[0])
        if (th, tw) == (arr.shape[0], arr.shape[1]):
            out.append(arr)
        else:
            out.append(np.asarray(
                Image.fromarray(arr).resize((tw, th), Image.BILINEAR)))
    return out


def resize_images(arrays) -> list:
    """Routed batch resize over decoded RGB arrays — the one seam both the
    media processor and bench.py measure. Device kernel when the sticky
    verdict allows (first warm batch is timed against PIL on the same
    arrays); scalar PIL otherwise. Raises only if the device path dies
    mid-call — callers fall back to the full scalar pipeline then."""
    import time as _time

    from ...ops.resize_jax import resize_batch_host

    if not _device_resize_allowed():
        return _pil_resize_all(arrays)
    if _DEVICE_VERDICT["value"] is None:
        # EVERY device call synchronizes while the verdict is open — a
        # concurrent unmeasured batch would otherwise share the device with
        # the timed probe and distort the measurement
        with _VERDICT_LOCK:
            if (_DEVICE_VERDICT["value"] is None
                    and len(arrays) >= _VERDICT_MIN_BATCH):
                # measure the WARM device rate: run once for the compile,
                # once for the timing, score against scalar. Either way THIS
                # batch's device outputs are valid (dimension-identical), so
                # nothing is recomputed — only future batches change route.
                resize_batch_host(arrays)
                t0 = _time.perf_counter()
                thumbs = resize_batch_host(arrays)
                _DEVICE_VERDICT["value"] = _measure_device_verdict(
                    arrays, _time.perf_counter() - t0)
                return thumbs
            if _DEVICE_VERDICT["value"] is False:
                return _pil_resize_all(arrays)
            return resize_batch_host(arrays)
    return resize_batch_host(arrays)


#: images decoded+resized+encoded per device call: bounds the pad-and-mask
#: batch (resize_batch_host pads every lane to the batch max and rounds the
#: count to a power of two — 256 lanes of 1024² would be ~0.8 GB of uint8
#: before the kernel's float intermediates) AND the decoded-array working
#: set, while still amortizing one dispatch over dozens of images
RESIZE_SUB_BATCH = 32


def generate_thumbnails_batched(entries, data_dir: str | Path,
                                allow_device: bool = True):
    """Batch thumbnail generation: host decode → routed bilinear-resize in
    RESIZE_SUB_BATCH chunks → host WebP encode.

    ``entries``: [(source_path, cas_id, extension)]; returns {cas_id: Path}
    for every thumbnail produced. Videos and failed decodes fall back to the
    scalar path. The per-image outputs are dimension-identical to the scalar
    PIL path (same √(area) target math, target_dims).

    Routing is get_hasher-style hybrid (``resize_images``): no accelerator →
    scalar PIL outright; with one, the first (warm) device batch is timed
    against a scalar probe on the same decoded arrays and the sticky
    per-process verdict routes every later call — the caller always gets its
    thumbnails over whichever path measured fastest. ``allow_device=False``
    (the tpuThumbnails feature left off) skips the device unconditionally.
    """
    from PIL import Image

    from ...utils.jax_guard import ensure_jax_safe

    if not allow_device:
        return _scalar_all(entries, data_dir)
    ensure_jax_safe()  # the device gate (routing below reads it)
    if not _device_resize_allowed():
        return _scalar_all(entries, data_dir)

    out_paths: dict[str, Path] = {}
    todo = []  # (source, cas_id, out_path, ext) still needing a thumbnail
    for source, cas_id, ext in entries:
        out = thumbnail_path(data_dir, cas_id)
        if out.exists():
            out_paths[cas_id] = out
            continue
        ext = (ext or Path(source).suffix.lstrip(".")).lower()
        if ext in THUMBNAILABLE_VIDEO_EXTENSIONS:
            made = generate_thumbnail(source, data_dir, cas_id, ext)
            if made is not None:
                out_paths[cas_id] = made
            continue
        todo.append((source, cas_id, out, ext))

    for start in range(0, len(todo), RESIZE_SUB_BATCH):
        sub = todo[start : start + RESIZE_SUB_BATCH]
        batch_arrays = []
        batch_meta = []
        for source, cas_id, out, ext in sub:
            try:
                batch_arrays.append(_decode_for_device(Path(source)))
                batch_meta.append((source, cas_id, out, ext))
            except Exception as e:
                logger.warning("decode failed for %s: %s", source, e)
        if not batch_arrays:
            continue
        try:
            thumbs = resize_images(batch_arrays)
        except Exception as e:
            logger.warning("device resize failed (%s); scalar fallback", e)
            out_paths.update(_scalar_all(
                [(s, c, e3) for s, c, _o, e3 in batch_meta], data_dir))
            continue
        for (_source, cas_id, out, _ext), thumb in zip(batch_meta, thumbs):
            try:
                faults.inject("thumbnail", key=cas_id)
                with atomic_path(out) as tmp:
                    _save_webp(Image.fromarray(thumb), tmp)
                out_paths[cas_id] = out
            except Exception as e:
                if is_disk_full(e):
                    note_disk_full("thumbnail")
                logger.warning("thumbnail encode failed for %s: %s", cas_id, e)
    return out_paths
