#!/usr/bin/env python
"""Image-folder -> RecordIO packer (reference ``tools/im2rec.py``).

Two modes, CLI-compatible with the reference:

    # 1) generate a .lst (index<TAB>label<TAB>relpath) from a folder tree
    python tools/im2rec.py --list prefix image_root [--recursive]
                           [--train-ratio R] [--test-ratio R]

    # 2) pack a .lst into prefix.rec + prefix.idx
    python tools/im2rec.py prefix image_root [--resize N] [--quality Q]
                           [--encoding .jpg|.png|.npy] [--pack-label]

The .rec wire format is dmlc RecordIO (src/io/recordio.cc — the C++
reader speaks it) with IRHeader-packed JPEG/PNG payloads, so records
written here read back through ImageRecordIter / mx.image.ImageIter and
through reference readers.
"""
from __future__ import annotations

import argparse
import os
import random
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# host-side tool: decode/augment/pack never needs an accelerator, and
# must not take the chip from the process that does — pin the CPU
# platform up front
try:
    import jax

    jax.config.update("jax_platforms", "cpu")
except Exception:
    pass

EXTS = (".jpg", ".jpeg", ".png", ".bmp")


def list_images(root: str, recursive: bool):
    """Yield (relpath, label) with labels = sorted top-level folder index
    (reference im2rec.py list_image)."""
    if recursive:
        cats = {}
        for path, _dirs, files in sorted(os.walk(root, followlinks=True)):
            for name in sorted(files):
                if name.lower().endswith(EXTS):
                    folder = os.path.relpath(path, root).split(os.sep)[0]
                    if folder not in cats:
                        cats[folder] = len(cats)
                    yield (os.path.relpath(os.path.join(path, name), root),
                           cats[folder])
    else:
        for i, name in enumerate(sorted(os.listdir(root))):
            if name.lower().endswith(EXTS):
                yield name, 0


def write_list(prefix: str, image_list, train_ratio: float, test_ratio: float,
               shuffle: bool):
    items = list(image_list)
    if shuffle:
        random.shuffle(items)
    n = len(items)
    n_test = int(n * test_ratio)
    n_train = int(n * train_ratio)
    chunks = {}
    if train_ratio + test_ratio < 1.0 and train_ratio < 1.0:
        chunks[f"{prefix}_train.lst"] = items[n_test:n_test + n_train] \
            if train_ratio < 1 - test_ratio else items[n_test:]
        chunks[f"{prefix}_val.lst"] = items[n_test + n_train:]
        if n_test:
            chunks[f"{prefix}_test.lst"] = items[:n_test]
    else:
        chunks[f"{prefix}.lst"] = items
    for fname, chunk in chunks.items():
        if not chunk and fname != f"{prefix}.lst":
            continue
        with open(fname, "w") as f:
            for i, (path, label) in enumerate(chunk):
                f.write(f"{i}\t{label}\t{path}\n")
        print(f"wrote {len(chunk)} entries to {fname}")


def read_list(path_in: str):
    with open(path_in) as f:
        for line in f:
            parts = line.strip().split("\t")
            if len(parts) < 3:
                continue
            yield int(parts[0]), [float(x) for x in parts[1:-1]], parts[-1]


def make_rec(prefix: str, root: str, args) -> None:
    import numpy as onp

    from mxnet_tpu import recordio

    lst = prefix + ".lst"
    if not os.path.exists(lst):
        raise SystemExit(f"{lst} not found — run --list first")
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    n = 0
    for idx, labels, relpath in read_list(lst):
        from mxnet_tpu.image import imread, imresize, resize_short

        img = imread(os.path.join(root, relpath))
        if args.resize:
            img = resize_short(img, args.resize)
        if args.center_crop:
            from mxnet_tpu.image import center_crop

            s = min(img.shape[0], img.shape[1])
            img, _ = center_crop(img, (s, s))
        label = labels[0] if len(labels) == 1 and not args.pack_label \
            else onp.asarray(labels, onp.float32)
        header = recordio.IRHeader(0, label, idx, 0)
        payload = recordio.pack_img(header, img.asnumpy(),
                                    quality=args.quality,
                                    img_fmt=args.encoding)
        rec.write_idx(idx, payload)
        n += 1
    rec.close()
    print(f"packed {n} images into {prefix}.rec (+ .idx)")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("prefix", help="output prefix (or .lst prefix)")
    ap.add_argument("root", help="image folder root")
    ap.add_argument("--list", action="store_true",
                    help="generate .lst instead of packing .rec")
    ap.add_argument("--recursive", action="store_true",
                    help="label by top-level subfolder")
    ap.add_argument("--shuffle", type=int, default=1)
    ap.add_argument("--train-ratio", type=float, default=1.0)
    ap.add_argument("--test-ratio", type=float, default=0.0)
    ap.add_argument("--resize", type=int, default=0,
                    help="resize shorter side to N before packing")
    ap.add_argument("--center-crop", action="store_true")
    ap.add_argument("--quality", type=int, default=95)
    ap.add_argument("--encoding", default=".jpg",
                    choices=[".jpg", ".jpeg", ".png", ".npy"])
    ap.add_argument("--pack-label", action="store_true",
                    help="store the full float label vector")
    args = ap.parse_args()
    if args.list:
        write_list(args.prefix, list_images(args.root, args.recursive),
                   args.train_ratio, args.test_ratio, bool(args.shuffle))
    else:
        make_rec(args.prefix, args.root, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
