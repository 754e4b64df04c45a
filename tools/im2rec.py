"""im2rec: build RecordIO packs from image directories (reference:
tools/im2rec.py — same .lst / .rec / .idx formats, PIL instead of OpenCV).

Usage:
    python tools/im2rec.py PREFIX IMAGE_ROOT --list     # write PREFIX.lst
    python tools/im2rec.py PREFIX IMAGE_ROOT            # .lst -> .rec/.idx

The .lst format matches the reference: ``index\\tlabel\\trelative/path``.
Labels come from sorted subdirectory names (one class per subdir), like the
reference's --recursive mode.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# host-side tool: never take the chip (one process at a time may hold it)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

EXTS = (".jpg", ".jpeg", ".png", ".bmp")


def make_list(prefix, root):
    classes = sorted(
        d for d in os.listdir(root)
        if os.path.isdir(os.path.join(root, d)))
    label_of = {c: i for i, c in enumerate(classes)}
    entries = []
    if classes:
        for c in classes:
            cdir = os.path.join(root, c)
            for fn in sorted(os.listdir(cdir)):
                if fn.lower().endswith(EXTS):
                    entries.append((label_of[c], os.path.join(c, fn)))
    else:  # flat directory, label 0
        for fn in sorted(os.listdir(root)):
            if fn.lower().endswith(EXTS):
                entries.append((0, fn))
    lst_path = prefix + ".lst"
    with open(lst_path, "w") as f:
        for i, (label, rel) in enumerate(entries):
            f.write(f"{i}\t{float(label)}\t{rel}\n")
    print(f"wrote {len(entries)} entries to {lst_path}")
    return lst_path


def read_list(lst_path):
    with open(lst_path) as f:
        for line in f:
            parts = line.strip().split("\t")
            if len(parts) >= 3:
                yield int(parts[0]), float(parts[1]), parts[-1]


def make_rec(prefix, root, quality=95, resize=None):
    import numpy as np
    from PIL import Image
    from mxnet_tpu import recordio

    lst_path = prefix + ".lst"
    if not os.path.exists(lst_path):
        make_list(prefix, root)
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    n = 0
    for idx, label, rel in read_list(lst_path):
        img = Image.open(os.path.join(root, rel)).convert("RGB")
        if resize:
            w, h = img.size
            scale = resize / min(w, h)
            img = img.resize((int(round(w * scale)), int(round(h * scale))))
        header = recordio.IRHeader(0, label, idx, 0)
        fmt = ".png" if rel.lower().endswith(".png") else ".jpg"
        rec.write_idx(idx, recordio.pack_img(header, np.asarray(img),
                                             quality=quality, img_fmt=fmt))
        n += 1
    rec.close()
    print(f"packed {n} images into {prefix}.rec (+.idx)")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("prefix")
    ap.add_argument("root")
    ap.add_argument("--list", action="store_true",
                    help="only generate the .lst file")
    ap.add_argument("--quality", type=int, default=95)
    ap.add_argument("--resize", type=int, default=None,
                    help="resize shorter edge to this many pixels")
    args = ap.parse_args()
    if args.list:
        make_list(args.prefix, args.root)
    else:
        make_rec(args.prefix, args.root, args.quality, args.resize)


if __name__ == "__main__":
    main()
