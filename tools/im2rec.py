#!/usr/bin/env python
"""im2rec: image folder -> .lst / .rec / .idx (parity: the reference's
tools/im2rec.py data-prep CLI).

Labels come from the immediate subdirectory of `root` (sorted name order,
like the reference's folder walk); pass an existing .lst to pack a curated
split instead. Images are re-encoded to JPEG at --quality (and optionally
--resize shortest side) so training-time decode is uniform — the
reference's offline-preprocessing recipe that keeps the input pipeline
chip-bound instead of decode-bound.

Usage:
  python tools/im2rec.py PREFIX ROOT [--list] [--resize N] [--quality Q]
                                     [--exts .jpg,.jpeg,.png]

  --list       only generate PREFIX.lst (index \t label \t relpath)
  otherwise    read/auto-generate PREFIX.lst and write PREFIX.rec + .idx
"""
import argparse
import os
import sys

# host-only tool: never initialize an accelerator backend (the framework
# import would otherwise register the TPU platform for pure CPU work)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_list(root, exts):
    """[(index, label, relpath)] — labels by sorted subdirectory name."""
    classes = sorted(d for d in os.listdir(root)
                     if os.path.isdir(os.path.join(root, d)))
    label_of = {c: float(i) for i, c in enumerate(classes)}
    entries = []
    i = 0
    for c in classes:
        cdir = os.path.join(root, c)
        for f in sorted(os.listdir(cdir)):
            if os.path.splitext(f)[1].lower() in exts:
                entries.append((i, label_of[c], os.path.join(c, f)))
                i += 1
    if not entries:
        raise SystemExit(f"no images with extensions {sorted(exts)} under "
                         f"{root!r}")
    return entries


def write_list(path, entries):
    with open(path, "w") as f:
        for idx, label, rel in entries:
            f.write(f"{idx}\t{label:g}\t{rel}\n")


def read_list(path):
    """idx \t label... \t relpath — multi-label rows keep the full label
    vector (the reference's detection/multi-task .lst format)."""
    out = []
    with open(path) as f:
        for ln, line in enumerate(f, 1):
            if not line.strip():
                continue
            parts = line.rstrip("\n").split("\t")
            try:
                if len(parts) < 3:
                    raise ValueError("need idx, label(s), path")
                labels = [float(v) for v in parts[1:-1]]
                label = labels[0] if len(labels) == 1 else labels
                out.append((int(parts[0]), label, parts[-1]))
            except (ValueError, IndexError):
                raise SystemExit(f"{path}:{ln}: malformed .lst line "
                                 f"{line.rstrip()!r}")
    return out


def pack(prefix, root, entries, resize, quality):
    import numpy as np
    from PIL import Image

    from incubator_mxnet_tpu import recordio

    writer = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec",
                                        "w")
    n = skipped = 0
    for idx, label, rel in entries:
        try:
            img = Image.open(os.path.join(root, rel)).convert("RGB")
            if resize:
                w, h = img.size
                s = resize / min(w, h)
                img = img.resize((max(1, round(w * s)),
                                  max(1, round(h * s))), Image.BILINEAR)
            # recordio.pack handles list labels (float32 vector + flag)
            payload = recordio.pack_img(
                recordio.IRHeader(0, label, idx, 0),
                np.asarray(img, np.uint8), quality=quality)
        except Exception as e:  # noqa: BLE001 — one bad image must not
            skipped += 1        # abort an hours-long pack (reference logs
            print(f"skipping {rel}: {type(e).__name__}: {e}",
                  file=sys.stderr)      # and continues the same way)
            continue
        writer.write_idx(idx, payload)
        n += 1
        if n % 1000 == 0:
            print(f"packed {n} images", file=sys.stderr)
    writer.close()
    msg = f"wrote {n} records -> {prefix}.rec / {prefix}.idx"
    if skipped:
        msg += f" ({skipped} unreadable images skipped)"
    print(msg)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("prefix", help="output prefix (PREFIX.lst/.rec/.idx)")
    p.add_argument("root", help="image folder (class subdirectories)")
    p.add_argument("--list", action="store_true",
                   help="only generate PREFIX.lst")
    p.add_argument("--resize", type=int, default=0,
                   help="resize shortest side to N pixels (0 = keep)")
    p.add_argument("--quality", type=int, default=95)
    p.add_argument("--exts", default=".jpg,.jpeg,.png",
                   help="comma-separated image extensions")
    a = p.parse_args(argv)
    exts = {e if e.startswith(".") else "." + e
            for e in a.exts.lower().split(",")}

    lst = a.prefix + ".lst"
    if a.list or not os.path.exists(lst):
        entries = make_list(a.root, exts)
        write_list(lst, entries)
        print(f"wrote {len(entries)} entries -> {lst}")
        if a.list:
            return
    entries = read_list(lst)
    pack(a.prefix, a.root, entries, a.resize, a.quality)


if __name__ == "__main__":
    main()
