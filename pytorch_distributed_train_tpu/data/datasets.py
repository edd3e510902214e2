"""Datasets for the acceptance matrix (BASELINE.json:7-11).

Two dataset shapes, mirroring the torch Dataset split the reference loader
consumes (map-style, torch:utils/data/dataloader.py):

- **ArrayDataset** — whole dataset in host RAM as numpy arrays; `get_batch`
  is one fancy-index + vectorized augment (CIFAR-10, synthetic).
- **ItemDataset** — per-item `get_item(i)` (JPEG decode + augment for
  ImageNet folders); the loader maps it over a thread pool, standing in for
  DataLoader's worker processes (SURVEY C17) — threads suffice because
  PIL/numpy release the GIL in the decode/resize hot path.

All image batches are NHWC float32, normalized; the device-side bf16 cast
happens inside the jitted step (precision policy, SURVEY C18).
"""

from __future__ import annotations

import os
import pickle
from typing import Iterable

import numpy as np

# Standard normalization constants (the reference-era torchvision recipe).
CIFAR_MEAN = np.array([0.4914, 0.4822, 0.4465], np.float32)
CIFAR_STD = np.array([0.2470, 0.2435, 0.2616], np.float32)
IMAGENET_MEAN = np.array([0.485, 0.456, 0.406], np.float32)
IMAGENET_STD = np.array([0.229, 0.224, 0.225], np.float32)


class ArrayDataset:
    """In-RAM dataset: dict of equal-length numpy arrays."""

    is_item_style = False

    def __init__(self, arrays: dict[str, np.ndarray]):
        lens = {k: len(v) for k, v in arrays.items()}
        if len(set(lens.values())) != 1:
            raise ValueError(f"ragged arrays: {lens}")
        self.arrays = arrays

    def __len__(self) -> int:
        return len(next(iter(self.arrays.values())))

    def get_batch(self, idx: np.ndarray, rng: np.random.Generator, train: bool) -> dict:
        return {k: v[idx] for k, v in self.arrays.items()}


def _crop_flip(images: np.ndarray, pad: int, ys, xs, flips) -> np.ndarray:
    """Reflect-pad random crop + hflip with precomputed draws — the numpy
    reference for the native kernel (imgops.augment_batch minus normalize)."""
    B, H, W, _ = images.shape
    padded = np.pad(images, ((0, 0), (pad,) * 2, (pad,) * 2, (0, 0)),
                    mode="reflect")
    out = np.empty_like(images)
    for i in range(B):
        img = padded[i, ys[i]: ys[i] + H, xs[i]: xs[i] + W]
        out[i] = img[:, ::-1] if flips[i] else img
    return out


class U8ImageDataset(ArrayDataset):
    """uint8 image storage + fused native augment/normalize (native/imgops).

    Keeps the dataset in RAM at 1/4 the float32 footprint and runs the
    reflect-pad crop + hflip + u8→f32 normalize as ONE multithreaded C++
    pass per batch (SURVEY C17 native equivalent). Falls back to the numpy
    path when the native build is unavailable — batch values are identical
    either way (both implement reflect-101 padding then (x/255-mean)/std).
    """

    def __init__(self, images_u8: np.ndarray | None, labels: np.ndarray,
                 mean: np.ndarray, std: np.ndarray, augment: bool,
                 pad: int = 4, randaugment=None, raw_u8: bool = False):
        # images_u8=None is the storage-elsewhere subclass hook (the
        # packed cache mmaps its pixels): _read_images is overridden and
        # only labels live in self.arrays.
        arrays = {"label": labels}
        if images_u8 is not None:
            arrays["image"] = images_u8
        super().__init__(arrays)
        self.mean, self.std = mean, std
        self.do_augment = augment
        self.pad = pad
        self.randaugment = randaugment if augment else None
        # raw_u8 (data.device_augment): ship uint8 pixels untouched —
        # crop/flip/RandAugment/normalize move into the jitted step
        # (ops/device_augment.py), so the host's augment share collapses
        # to the fancy-index read.
        self.raw_u8 = raw_u8
        self._ra_pool = None

    def __getstate__(self):
        # Thread pools don't pickle (grain's worker processes pickle the
        # dataset); it is rebuilt lazily in the worker.
        state = self.__dict__.copy()
        state["_ra_pool"] = None
        return state

    def _randaugment_batch(self, imgs_u8: np.ndarray, rng) -> np.ndarray:
        """RandAugment each image on a thread pool (PIL releases the GIL;
        a serial loop here would stall the single producer thread and make
        training input-bound). Per-image seeds are drawn up-front from the
        batch rng, so the result is deterministic regardless of thread
        scheduling."""
        from concurrent.futures import ThreadPoolExecutor

        from pytorch_distributed_train_tpu.data.augment import (
            apply_randaugment_u8,
        )

        seeds = rng.integers(np.iinfo(np.int64).max, size=len(imgs_u8))
        if len(imgs_u8) <= 2:
            # grain's per-record path calls with a single image — skip the
            # pool (a 16-thread executor per worker process for zero
            # parallelism otherwise).
            return np.stack([
                apply_randaugment_u8(im, self.randaugment,
                                     np.random.default_rng(s))
                for im, s in zip(imgs_u8, seeds)
            ])
        if self._ra_pool is None:
            self._ra_pool = ThreadPoolExecutor(
                max_workers=min(16, os.cpu_count() or 4))
        return np.stack(list(self._ra_pool.map(
            lambda args: apply_randaugment_u8(
                args[0], self.randaugment, np.random.default_rng(args[1])),
            zip(imgs_u8, seeds),
        )))

    def _read_images(self, idx) -> np.ndarray:
        """Pixel gather for a batch — overridden by the packed cache
        (mmap'd strided read instead of an in-RAM fancy index)."""
        return self.arrays["image"][idx]

    def get_batch(self, idx, rng, train):
        from pytorch_distributed_train_tpu.native import imgops
        from pytorch_distributed_train_tpu.obs.perf import stage

        with stage("read"):
            imgs = self._read_images(idx)
        if self.raw_u8:
            # Device-side augmentation path: the read IS the whole host
            # cost; pixels leave as uint8 (4x less h2d traffic than the
            # normalized f32 batch they replace).
            return {"image": np.ascontiguousarray(imgs),
                    "label": self.arrays["label"][idx]}
        B, H, W, C = imgs.shape
        with stage("augment"):
            return self._augment_batch(imgs, idx, rng, train, B, imgops)

    def _augment_batch(self, imgs, idx, rng, train, B, imgops):
        if train and self.do_augment:
            ys = rng.integers(0, 2 * self.pad + 1, size=B)
            xs = rng.integers(0, 2 * self.pad + 1, size=B)
            flips = rng.random(B) < 0.5
            if self.randaugment is not None:
                # torchvision recipe order: crop → flip → RandAugment →
                # normalize. RandAugment needs uint8 pixels, so the fused
                # native crop+normalize pass can't be used; crop/flip on u8,
                # augment, then normalize (native when available).
                cropped = _crop_flip(imgs, self.pad, ys, xs, flips)
                auged = self._randaugment_batch(cropped, rng)
                if imgops.available():
                    out = imgops.normalize_batch(auged, self.mean, self.std)
                else:
                    out = (auged.astype(np.float32) / 255.0 - self.mean) / self.std
            elif imgops.available():
                out = imgops.augment_batch(
                    imgs, self.pad, ys, xs, flips, self.mean, self.std)
            else:
                out = _crop_flip(imgs.astype(np.float32), self.pad, ys, xs,
                                 flips)
                out = (out / 255.0 - self.mean) / self.std
        elif imgops.available():
            out = imgops.normalize_batch(imgs, self.mean, self.std)
        else:
            out = (imgs.astype(np.float32) / 255.0 - self.mean) / self.std
        return {"image": out, "label": self.arrays["label"][idx]}


# ------------------------------------------------------------------ CIFAR-10

def load_cifar10(data_dir: str, train: bool, randaugment=None) -> ArrayDataset:
    """Reads the standard python-pickle CIFAR-10 batches (cifar-10-batches-py).

    The reference's config 1 dataset (BASELINE.json:7). Falls back to a
    deterministic synthetic stand-in when no data ships in the sandbox, so
    the preset stays runnable end-to-end.
    """
    base = _find_cifar_dir(data_dir)
    if base is None:
        return synthetic_images(50000 if train else 10000, 32, 10, seed=0 if train else 1)
    files = (
        [f"data_batch_{i}" for i in range(1, 6)] if train else ["test_batch"]
    )
    xs, ys = [], []
    for f in files:
        with open(os.path.join(base, f), "rb") as fh:
            d = pickle.load(fh, encoding="bytes")
        xs.append(d[b"data"])
        ys.append(np.asarray(d[b"labels"], np.int32))
    x = np.ascontiguousarray(
        np.concatenate(xs).reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
    )  # NHWC uint8 — normalization is fused into the per-batch native pass
    y = np.concatenate(ys)
    return U8ImageDataset(x, y, CIFAR_MEAN, CIFAR_STD, augment=train,
                          randaugment=randaugment)


def _find_cifar_dir(data_dir: str) -> str | None:
    if not data_dir:
        return None
    for cand in (data_dir, os.path.join(data_dir, "cifar-10-batches-py")):
        if os.path.exists(os.path.join(cand, "data_batch_1")):
            return cand
    return None


# ---------------------------------------------------------------- synthetic

def synthetic_images(size: int, image_size: int, num_classes: int, seed: int = 0) -> ArrayDataset:
    """Deterministic fake image classification data (throughput benches and
    the sandbox fallback — no augment, already 'normalized')."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((size, image_size, image_size, 3), np.float32)
    y = rng.integers(0, num_classes, size=size).astype(np.int32)
    return ArrayDataset({"image": x, "label": y})


def synthetic_lm(size: int, seq_len: int, vocab_size: int, seed: int = 0) -> ArrayDataset:
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, vocab_size, size=(size, seq_len)).astype(np.int32)
    return ArrayDataset({"input_ids": ids})


def synthetic_dpo(size: int, seq_len: int, vocab_size: int,
                  prompt_len: int | None = None,
                  seed: int = 0) -> ArrayDataset:
    """Random preference pairs for DPO (losses.make_dpo_loss): each row
    holds a shared prompt followed by two different continuations,
    ``input_ids`` (2, S) stacked [chosen, rejected], ``loss_mask``
    marking the continuation positions."""
    rng = np.random.default_rng(seed)
    p = prompt_len if prompt_len is not None else seq_len // 2
    prompt = rng.integers(0, vocab_size, (size, 1, p))
    conts = rng.integers(0, vocab_size, (size, 2, seq_len - p))
    ids = np.concatenate(
        [np.broadcast_to(prompt, (size, 2, p)), conts], axis=2)
    mask = np.zeros((size, 2, seq_len), np.float32)
    mask[:, :, p:] = 1.0
    return ArrayDataset({"input_ids": ids.astype(np.int32),
                         "loss_mask": mask})


def synthetic_seq2seq(size: int, src_len: int, tgt_len: int,
                      vocab_size: int, seed: int = 0) -> ArrayDataset:
    """Random source/target pairs in the T5 convention:
    decoder_input_ids = labels shifted right with a 0 start token
    (HF `_shift_right`; id 0 is T5's pad/decoder-start)."""
    rng = np.random.default_rng(seed)
    src = rng.integers(1, vocab_size, size=(size, src_len)).astype(np.int32)
    labels = rng.integers(1, vocab_size,
                          size=(size, tgt_len)).astype(np.int32)
    dec_in = np.concatenate(
        [np.zeros((size, 1), np.int32), labels[:, :-1]], axis=1)
    return ArrayDataset({"input_ids": src, "decoder_input_ids": dec_in,
                         "labels": labels})


class MLMDataset(ArrayDataset):
    """Token sequences + BERT-style dynamic masking applied at batch time.

    Masking follows the original recipe the reference's config 4 targets
    (BASELINE.json:10): select `mlm_prob` of tokens; 80% → [MASK], 10% →
    random token, 10% → unchanged. Labels carry original ids everywhere;
    `label_weights` marks the selected positions (static shapes — see
    losses.mlm_xent).
    """

    def __init__(self, input_ids: np.ndarray, attention_mask: np.ndarray,
                 vocab_size: int, mlm_prob: float = 0.15, mask_id: int = 103):
        super().__init__({"input_ids": input_ids, "attention_mask": attention_mask})
        self.vocab_size = vocab_size
        self.mlm_prob = mlm_prob
        self.mask_id = mask_id

    def get_batch(self, idx, rng, train):
        ids = self.arrays["input_ids"][idx]
        mask = self.arrays["attention_mask"][idx]
        labels = ids.copy()
        B, S = ids.shape
        sel = (rng.random((B, S)) < self.mlm_prob) & (mask > 0)
        action = rng.random((B, S))
        masked = ids.copy()
        masked[sel & (action < 0.8)] = self.mask_id
        rand_pos = sel & (action >= 0.8) & (action < 0.9)
        masked[rand_pos] = rng.integers(
            0, self.vocab_size, size=int(rand_pos.sum())
        ).astype(ids.dtype)
        return {
            "input_ids": masked,
            "attention_mask": mask,
            "labels": labels,
            "label_weights": sel.astype(np.float32),
        }


def synthetic_mlm(size: int, seq_len: int, vocab_size: int, mlm_prob: float,
                  seed: int = 0) -> MLMDataset:
    rng = np.random.default_rng(seed)
    low = min(200, vocab_size // 2)  # skip the "special token" id range
    ids = rng.integers(low, vocab_size, size=(size, seq_len)).astype(np.int32)
    mask = np.ones_like(ids)
    return MLMDataset(ids, mask, vocab_size, mlm_prob)


# ------------------------------------------------------------ ImageNet folder

class ImageFolderDataset:
    """ImageNet-layout folder (class-per-subdir); per-item JPEG decode +
    RandomResizedCrop/flip (train) or Resize+CenterCrop (eval).

    The reference's config 2/3 dataset (BASELINE.json:8-9). Item-style: the
    loader maps get_item over its thread pool (SURVEY C17 equivalent).
    """

    is_item_style = True

    def __init__(self, root: str, image_size: int, train: bool,
                 randaugment=None, raw_u8: bool = False):
        from PIL import Image  # noqa: F401  (verify import early)

        self.root = root
        self.image_size = image_size
        self.train = train
        self.randaugment = randaugment if train else None
        # raw_u8 (data.device_augment): decode + crop stay host-side
        # (RandomResizedCrop IS the decode-adjacent resample); flip,
        # RandAugment and normalize move into the jitted step, and the
        # item leaves as HWC uint8.
        self.raw_u8 = raw_u8
        classes = sorted(
            d for d in os.listdir(root) if os.path.isdir(os.path.join(root, d))
        )
        self.class_to_idx = {c: i for i, c in enumerate(classes)}
        self.samples: list[tuple[str, int]] = []
        for c in classes:
            cdir = os.path.join(root, c)
            for f in sorted(os.listdir(cdir)):
                if f.lower().endswith((".jpg", ".jpeg", ".png")):
                    self.samples.append((os.path.join(cdir, f), self.class_to_idx[c]))

    def __len__(self) -> int:
        return len(self.samples)

    def _open_sample(self, i: int):
        """→ (PIL.Image, label). Overridden by the tar-shard variant."""
        from PIL import Image

        path, label = self.samples[i]
        return Image.open(path), label

    def get_item(self, i: int, rng: np.random.Generator) -> dict:
        from PIL import Image

        from pytorch_distributed_train_tpu.obs.perf import stage

        # Stage attribution (obs/perf.py): read = storage bytes → PIL
        # handle, decode = compressed bytes → pixels (convert forces the
        # lazy PIL load), augment = crop/flip/RandAugment/normalize.
        with stage("read"):
            pil, label = self._open_sample(i)
        with pil as im:
            with stage("decode"):
                im = im.convert("RGB")
            with stage("augment"):
                if self.train:
                    im = _random_resized_crop(im, self.image_size, rng)
                    if not self.raw_u8:
                        if rng.random() < 0.5:
                            im = im.transpose(Image.FLIP_LEFT_RIGHT)
                        if self.randaugment is not None:
                            im = self.randaugment(im, rng)
                else:
                    im = _center_crop(im, self.image_size)
                x_u8 = np.asarray(im, np.uint8)
        if self.raw_u8:
            # device-augment mode: flip/RandAugment/normalize happen in
            # the jitted step; the host ships uint8.
            return {"image": x_u8, "label": np.int32(label)}
        from pytorch_distributed_train_tpu.native import imgops

        with stage("augment"):
            if imgops.available():
                x = imgops.normalize_batch(
                    x_u8[None], IMAGENET_MEAN, IMAGENET_STD, nthreads=1)[0]
            else:
                x = (x_u8.astype(np.float32) / 255.0
                     - IMAGENET_MEAN) / IMAGENET_STD
        return {"image": x, "label": np.int32(label)}


def write_jpeg_tar_shard(path: str, n: int, rng, *, start_key: int = 0,
                         size_range: tuple[int, int] = (256, 513),
                         fixed_size: int | None = None,
                         num_classes: int = 1000,
                         quality: int = 85) -> None:
    """Synthesize ONE WebDataset-convention tar shard of photo-like JPEGs.

    The single writer for the ``<key>.jpg + <key>.cls`` layout that
    :class:`TarShardImageDataset` reads — bench.py's decode arm,
    tools/sustained_drill.py, and the pipeline/grain tests all call this,
    so the shard contract lives in exactly one place. "Photo-like" =
    low-res noise upsampled smooth: JPEG entropy (and decode cost) tracks
    real photos, where raw noise is the pathological worst case.
    Writes directly to ``path`` — callers needing atomicity write to a
    temp name and rename.
    """
    import io
    import tarfile

    from PIL import Image

    with tarfile.open(path, "w") as tf:
        for k in range(n):
            if fixed_size is not None:
                W = H = fixed_size
            else:
                W = int(rng.integers(*size_range))
                H = int(rng.integers(*size_range))
            base = rng.integers(0, 256, (max(H // 8, 1), max(W // 8, 1), 3),
                                np.uint8)
            im = Image.fromarray(base).resize((W, H), Image.BILINEAR)
            buf = io.BytesIO()
            im.save(buf, "JPEG", quality=quality)
            data = buf.getvalue()
            info = tarfile.TarInfo(f"{start_key + k:06d}.jpg")
            info.size = len(data)
            tf.addfile(info, io.BytesIO(data))
            cls = str(int(rng.integers(0, num_classes))).encode()
            info = tarfile.TarInfo(f"{start_key + k:06d}.cls")
            info.size = len(cls)
            tf.addfile(info, io.BytesIO(cls))


class TarShardImageDataset(ImageFolderDataset):
    """WebDataset-convention tar shards: each ``.tar`` holds ``<key>.jpg``
    (or .jpeg/.png) + ``<key>.cls`` (class index as ASCII) pairs. The
    ImageNet-at-scale storage layout — thousands of sequential-read shards
    instead of a million tiny files (object stores and network filesystems
    hate the latter). Same decode/augment path as ImageFolderDataset.

    Random access: member offsets are indexed once at startup (tar headers
    only); reads then seek directly into the shard. File handles are
    per-thread and dropped on pickle, so the dataset works under both the
    thread loader and Grain worker processes."""

    def __init__(self, pattern: str, image_size: int, train: bool,
                 randaugment=None, native_decode: bool = False,
                 decode_threads: int = 0, raw_u8: bool = False):
        import glob as glob_mod
        import tarfile

        self.image_size = image_size
        self.train = train
        self.randaugment = randaugment if train else None
        # raw_u8 (device augment) needs uint8 out, which the fused
        # native decode+normalize kernel cannot produce — the PIL
        # per-item path carries this mode (see ImageFolderDataset).
        self.raw_u8 = raw_u8
        if raw_u8:
            native_decode = False
        self.shards = sorted(glob_mod.glob(pattern))
        if not self.shards:
            raise FileNotFoundError(
                f"data.data_dir matched no .tar shards: {pattern!r}")
        # samples: (shard_idx, jpg_offset, jpg_size, label)
        self.samples = []  # type: ignore[assignment]
        has_non_jpeg = False
        for si, shard in enumerate(self.shards):
            pairs: dict[str, dict] = {}
            # mode "r:" = uncompressed only — autodetected gzip shards
            # would index offsets into the DECOMPRESSED stream that the
            # raw-seek read path can't honor; fail fast here instead of
            # handing gzip bytes to PIL later.
            with tarfile.open(shard, "r:") as tf:
                for m in tf:
                    if not m.isfile():
                        continue
                    key, dot, ext = m.name.rpartition(".")
                    ext = ext.lower()
                    entry = pairs.setdefault(key, {})
                    if ext in ("jpg", "jpeg", "png"):
                        entry["img"] = (m.offset_data, m.size)
                        has_non_jpeg |= ext == "png"
                    elif ext == "cls":
                        f = tf.extractfile(m)
                        entry["label"] = int(f.read().strip())  # type: ignore[union-attr]
            for key in sorted(pairs):
                entry = pairs[key]
                if "img" in entry and "label" in entry:
                    off, size = entry["img"]
                    self.samples.append((si, off, size, entry["label"]))
        if not self.samples:
            raise ValueError(
                f"tar shards {self.shards} contain no (img, cls) pairs")
        # Native decode path (SURVEY §7.4.1): libjpeg batch decode + crop
        # resize + normalize in C++ threads instead of per-item PIL. Only
        # when every image is JPEG, RandAugment is off (PIL-op chain), and
        # the library builds — silently fall back otherwise: the knob is a
        # throughput choice, not a semantics one.
        self.native_decode = False
        self.decode_threads = decode_threads  # 0 → jpegdec.default_threads
        self._decode_failures = 0
        self._failure_warnings = 0
        if native_decode and not has_non_jpeg and self.randaugment is None:
            from pytorch_distributed_train_tpu.native import jpegdec

            self.native_decode = jpegdec.available()
        if self.native_decode:
            self.is_item_style = False  # loader calls get_batch instead
        import threading

        self._local = threading.local()

    def __getstate__(self):
        d = self.__dict__.copy()
        d.pop("_local", None)  # open handles never cross process forks
        return d

    def __setstate__(self, d):
        import threading

        self.__dict__.update(d)
        self._local = threading.local()

    _MAX_OPEN_PER_THREAD = 64

    def _handle(self, si: int):
        # LRU-bounded per-thread handle cache: random access touches every
        # shard eventually, and thousands-of-shards x N threads of open
        # fds would blow typical ulimits mid-epoch.
        files = getattr(self._local, "files", None)
        if files is None:
            files = self._local.files = {}
        fh = files.pop(si, None)
        if fh is None:
            if len(files) >= self._MAX_OPEN_PER_THREAD:
                oldest = next(iter(files))  # dict order = LRU order
                files.pop(oldest).close()
            fh = open(self.shards[si], "rb")
        files[si] = fh  # reinsert → most-recently-used position
        return fh

    def _open_sample(self, i: int):
        import io

        from PIL import Image

        si, off, size, label = self.samples[i]
        fh = self._handle(si)
        fh.seek(off)
        return Image.open(io.BytesIO(fh.read(size))), label

    def get_batch(self, idx, rng: np.random.Generator, train: bool) -> dict:
        """Native decode path: raw bytes out of the shard (Python, cheap) →
        one jpegdec call (C++ threads, no GIL) doing decode + crop-box
        bilinear resize + flip + normalize. Boxes come from the SAME
        _rrc_box/_center_box policy the PIL path uses; only the resampler
        differs (plain bilinear vs PIL's filtered resize — documented in
        native/jpegdec.cpp). Corrupt members decode to zeros rather than
        poisoning the epoch."""
        from pytorch_distributed_train_tpu.native import jpegdec
        from pytorch_distributed_train_tpu.obs.perf import stage

        blobs: list[bytes] = []
        labels = np.empty(len(idx), np.int32)
        with stage("read"):
            for n, i in enumerate(idx):
                si, off, size, label = self.samples[int(i)]
                fh = self._handle(si)
                fh.seek(off)
                blobs.append(fh.read(size))
                labels[n] = label
        dims = jpegdec.dims(blobs)
        B = len(blobs)
        boxes = np.empty((B, 4), np.float32)
        flips = np.zeros(B, bool)
        for n in range(B):
            W, H = int(dims[n, 0]), int(dims[n, 1])
            if W == 0 or H == 0:
                boxes[n] = (0.0, 0.0, 1.0, 1.0)  # corrupt: zeroed below
                continue
            if train:
                box = _rrc_box(W, H, rng)
                boxes[n] = box if box is not None else _center_box(W, H)
                flips[n] = rng.random() < 0.5
            else:
                boxes[n] = _center_box(W, H)
        # The fused native pass does decode + crop-resize + normalize in
        # one C++ call; it is attributed to `decode` whole (decode
        # dominates, and the fusion is the point — splitting it would
        # mean un-fusing the kernel to measure it).
        with stage("decode"):
            images, fails = jpegdec.decode_batch(
                blobs, boxes, flips, self.image_size,
                IMAGENET_MEAN, IMAGENET_STD, nthreads=self.decode_threads)
        if fails:
            # Zero-filled images keep real labels — survivable (one bad
            # sample must not kill an epoch) but must be LOUD: systematic
            # corruption silently degrading accuracy is the failure mode.
            self._decode_failures += fails
            if self._failure_warnings < 5:
                self._failure_warnings += 1
                import sys

                print(
                    f"[jpegdec] {fails} corrupt image(s) in batch "
                    f"(total {self._decode_failures} this dataset) — "
                    "zero-filled"
                    + ("; suppressing further warnings"
                       if self._failure_warnings == 5 else ""),
                    file=sys.stderr, flush=True)
        return {"image": images, "label": labels}


def _rrc_box(W: int, H: int, rng: np.random.Generator):
    """RandomResizedCrop box (x0, y0, w, h) in source coords, or None after
    10 failed attempts (caller falls back to center crop). Pure function of
    (dims, rng) so the PIL and native-decode paths draw identical boxes."""
    area = W * H
    for _ in range(10):
        target = area * rng.uniform(0.08, 1.0)
        ratio = np.exp(rng.uniform(np.log(3 / 4), np.log(4 / 3)))
        w = int(round(np.sqrt(target * ratio)))
        h = int(round(np.sqrt(target / ratio)))
        if 0 < w <= W and 0 < h <= H:
            x0 = int(rng.integers(0, W - w + 1))
            y0 = int(rng.integers(0, H - h + 1))
            return (x0, y0, w, h)
    return None


def _center_box(W: int, H: int):
    """Center-crop box equivalent of _center_crop's resize-then-crop: a
    centered square of side min(W,H)·224/256, resized to target by the
    caller. (Sub-pixel rounding differs from the PIL path's two-step
    resize; visually and statistically identical.)"""
    side = min(W, H) * 224.0 / 256.0
    return ((W - side) / 2.0, (H - side) / 2.0, side, side)


def _random_resized_crop(im, size: int, rng: np.random.Generator):
    from PIL import Image

    W, H = im.size
    box = _rrc_box(W, H, rng)
    if box is None:
        return _center_crop(im, size)
    x0, y0, w, h = box
    return im.resize((size, size), Image.BILINEAR, box=(x0, y0, x0 + w, y0 + h))


def _center_crop(im, size: int):
    from PIL import Image

    W, H = im.size
    scale = size / min(W, H) * 256 / 224  # resize shorter side to size*256/224
    im = im.resize((max(1, int(W * scale)), max(1, int(H * scale))), Image.BILINEAR)
    W, H = im.size
    x0, y0 = (W - size) // 2, (H - size) // 2
    return im.crop((x0, y0, x0 + size, y0 + size))


# ------------------------------------------------------------------ factory

def _build_randaugment(data_cfg, train: bool):
    if not train or data_cfg.randaugment_num_ops <= 0:
        return None
    # With device augment on, the RandAugment op space runs on-device
    # inside the jitted step (ops/device_augment.py) — a host-side PIL
    # chain here would double-augment.
    if getattr(data_cfg, "device_augment", False):
        return None
    from pytorch_distributed_train_tpu.data.augment import RandAugment

    return RandAugment(data_cfg.randaugment_num_ops,
                       data_cfg.randaugment_magnitude)


def _want_raw_u8(data_cfg) -> bool:
    return bool(getattr(data_cfg, "device_augment", False))


def _packed_or_none(data_cfg, train: bool):
    """data.packed_cache_dir: a valid packed cache for the split
    replaces the decode path (data/packed_cache.py — hit/miss counted
    in the registry); anything else falls through to the original
    dataset build."""
    cache_dir = getattr(data_cfg, "packed_cache_dir", "")
    if not cache_dir:
        return None
    from pytorch_distributed_train_tpu.data.packed_cache import (
        load_packed_if_present,
    )

    return load_packed_if_present(
        cache_dir, "train" if train else "val", augment=train,
        randaugment=_build_randaugment(data_cfg, train),
        verify=getattr(data_cfg, "packed_verify", False),
        raw_u8=_want_raw_u8(data_cfg))


def build_dataset(data_cfg, model_cfg, train: bool):
    name = data_cfg.dataset
    if name in ("cifar10", "imagenet_folder", "imagenet_tar"):
        packed = _packed_or_none(data_cfg, train)
        if packed is not None:
            return packed
    if name == "packed_images":
        # Direct packed-shard dataset: data_dir is a shard directory,
        # glob, or single file (tools/pack_dataset.py output).
        from pytorch_distributed_train_tpu.data.packed_cache import (
            PackedImageDataset,
        )

        return PackedImageDataset(
            data_cfg.data_dir, augment=train,
            randaugment=_build_randaugment(data_cfg, train),
            verify=getattr(data_cfg, "packed_verify", False),
            raw_u8=_want_raw_u8(data_cfg),
            split="train" if train else "val")
    if name == "cifar10":
        ds = load_cifar10(data_cfg.data_dir, train,
                          randaugment=_build_randaugment(data_cfg, train))
        if _want_raw_u8(data_cfg) and isinstance(ds, U8ImageDataset):
            ds.raw_u8 = True
        return ds
    if name == "synthetic_images":
        return synthetic_images(
            data_cfg.synthetic_size, model_cfg.image_size, model_cfg.num_classes,
            seed=0 if train else 1,
        )
    if name == "imagenet_folder":
        split = "train" if train else "val"
        root = os.path.join(data_cfg.data_dir, split)
        if not os.path.isdir(root):
            return synthetic_images(
                data_cfg.synthetic_size, model_cfg.image_size,
                model_cfg.num_classes, seed=0 if train else 1,
            )
        return ImageFolderDataset(root, model_cfg.image_size, train,
                                  randaugment=_build_randaugment(data_cfg, train),
                                  raw_u8=_want_raw_u8(data_cfg))
    if name == "imagenet_tar":
        # WebDataset-style shards: data_dir is a glob per split, e.g.
        # '/data/imagenet-{split}-*.tar' ({split} → train|val), or a
        # plain glob used for both splits.
        pattern = data_cfg.data_dir.replace(
            "{split}", "train" if train else "val")
        return TarShardImageDataset(
            pattern, model_cfg.image_size, train,
            randaugment=_build_randaugment(data_cfg, train),
            native_decode=data_cfg.native_decode,
            decode_threads=data_cfg.num_workers,
            raw_u8=_want_raw_u8(data_cfg))
    if name == "synthetic_lm":
        return synthetic_lm(
            data_cfg.synthetic_size, data_cfg.seq_len, model_cfg.vocab_size,
            seed=0 if train else 1,
        )
    if name == "synthetic_dpo":
        return synthetic_dpo(
            data_cfg.synthetic_size, data_cfg.seq_len,
            model_cfg.vocab_size, seed=0 if train else 1,
        )
    if name == "synthetic_seq2seq":
        return synthetic_seq2seq(
            data_cfg.synthetic_size, data_cfg.seq_len,
            data_cfg.tgt_seq_len or data_cfg.seq_len,
            model_cfg.vocab_size, seed=0 if train else 1,
        )
    if name == "text_lm":
        from pytorch_distributed_train_tpu.data.text import build_text_dataset

        return build_text_dataset(data_cfg, model_cfg, train, mlm=False)
    if name == "text_mlm":
        if data_cfg.text_files:
            from pytorch_distributed_train_tpu.data.text import (
                build_text_dataset,
            )

            return build_text_dataset(data_cfg, model_cfg, train, mlm=True)
        return synthetic_mlm(
            data_cfg.synthetic_size, data_cfg.seq_len, model_cfg.vocab_size,
            data_cfg.mlm_prob, seed=0 if train else 1,
        )
    raise KeyError(f"unknown dataset {name!r}")
