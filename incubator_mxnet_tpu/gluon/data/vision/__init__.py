"""gluon.data.vision: datasets + transforms (parity: python/mxnet/gluon/data/vision).

Zero-egress note: datasets read standard local files (idx/npz/binary); when
files are absent, MNIST/FashionMNIST/CIFAR fall back to deterministic
synthetic data with the real shapes/classes so examples and tests run
anywhere."""
from __future__ import annotations

import gzip
import os
import struct

import numpy as np

from .... import ndarray as nd
from ....recordio import unpack_img
from ....ndarray import NDArray
from .. import ArrayDataset, Dataset, RecordFileDataset
from . import transforms

__all__ = ["MNIST", "FashionMNIST", "CIFAR10", "CIFAR100",
           "ImageFolderDataset", "ImageRecordDataset", "transforms"]


def _read_idx(path):
    op = gzip.open if path.endswith(".gz") else open
    with op(path, "rb") as f:
        magic = int.from_bytes(f.read(4), "big")
        ndim = magic & 0xFF
        shape = [int.from_bytes(f.read(4), "big") for _ in range(ndim)]
        return np.frombuffer(f.read(), np.uint8).reshape(shape)


def _synthetic_images(n, shape, num_classes, seed):
    rng = np.random.RandomState(seed)
    labels = rng.randint(0, num_classes, n).astype(np.int32)
    h, w = shape[0], shape[1]
    c = shape[2] if len(shape) > 2 else 1
    X = np.zeros((n, h, w, c), np.uint8)
    for i, l in enumerate(labels):
        r0 = (2 + l * 2) % max(h - 6, 1)
        X[i, r0:r0 + 4, 2:w - 2] = 200
    X = np.clip(X + rng.randint(0, 40, X.shape), 0, 255).astype(np.uint8)
    return X.squeeze(-1) if c == 1 and len(shape) == 2 else X, labels


class _DownloadedDataset(Dataset):
    def __init__(self, root, train, transform):
        self._root = os.path.expanduser(root)
        self._train = train
        self._transform = transform
        self._data = None
        self._label = None
        self._get_data()

    def __getitem__(self, idx):
        img = nd.array(self._data[idx])
        label = self._label[idx]
        if self._transform is not None:
            return self._transform(img, label)
        return img, label

    def __len__(self):
        return len(self._label)


class MNIST(_DownloadedDataset):
    _files = {True: ("train-images-idx3-ubyte", "train-labels-idx1-ubyte"),
              False: ("t10k-images-idx3-ubyte", "t10k-labels-idx1-ubyte")}
    _synth_seed = 42

    def __init__(self, root="~/.mxtpu/datasets/mnist", train=True, transform=None):
        super().__init__(root, train, transform)

    def _get_data(self):
        img_f, lab_f = self._files[self._train]
        img_path = os.path.join(self._root, img_f)
        if os.path.exists(img_path) or os.path.exists(img_path + ".gz"):
            p = img_path if os.path.exists(img_path) else img_path + ".gz"
            lp = os.path.join(self._root, lab_f)
            lp = lp if os.path.exists(lp) else lp + ".gz"
            self._data = _read_idx(p).astype(np.float32)[..., None] / 1.0
            self._label = _read_idx(lp).astype(np.int32)
        else:
            n = 10000 if self._train else 2000
            X, y = _synthetic_images(n, (28, 28), 10, self._synth_seed)
            self._data = X[..., None].astype(np.float32)
            self._label = y


class FashionMNIST(MNIST):
    _synth_seed = 43

    def __init__(self, root="~/.mxtpu/datasets/fashion-mnist", train=True,
                 transform=None):
        _DownloadedDataset.__init__(self, root, train, transform)


class CIFAR10(_DownloadedDataset):
    _nclass = 10
    _synth_seed = 44

    def __init__(self, root="~/.mxtpu/datasets/cifar10", train=True, transform=None):
        super().__init__(root, train, transform)

    def _get_data(self):
        batches = ([f"data_batch_{i}" for i in range(1, 6)] if self._train
                   else ["test_batch"])
        paths = [os.path.join(self._root, "cifar-10-batches-py", b) for b in batches]
        if all(os.path.exists(p) for p in paths):
            import pickle
            xs, ys = [], []
            for p in paths:
                with open(p, "rb") as f:
                    d = pickle.load(f, encoding="bytes")
                xs.append(d[b"data"].reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1))
                ys.append(d[b"labels" if b"labels" in d else b"fine_labels"])
            self._data = np.concatenate(xs).astype(np.float32)
            self._label = np.concatenate(ys).astype(np.int32)
        else:
            n = 10000 if self._train else 2000
            X, y = _synthetic_images(n, (32, 32, 3), self._nclass, self._synth_seed)
            self._data = X.astype(np.float32)
            self._label = y


class CIFAR100(CIFAR10):
    _nclass = 100
    _synth_seed = 45

    def __init__(self, root="~/.mxtpu/datasets/cifar100", train=True, transform=None):
        _DownloadedDataset.__init__(self, root, train, transform)


class ImageFolderDataset(Dataset):
    """folder/class_name/*.png layout; decodes via PIL if available, else
    npy files."""

    def __init__(self, root, flag=1, transform=None):
        self._root = os.path.expanduser(root)
        self._transform = transform
        self.synsets = []
        self.items = []
        for folder in sorted(os.listdir(self._root)):
            path = os.path.join(self._root, folder)
            if not os.path.isdir(path):
                continue
            label = len(self.synsets)
            self.synsets.append(folder)
            for fname in sorted(os.listdir(path)):
                self.items.append((os.path.join(path, fname), label))

    def __getitem__(self, idx):
        path, label = self.items[idx]
        if path.endswith(".npy"):
            img = np.load(path)
        else:
            from PIL import Image
            img = np.asarray(Image.open(path).convert("RGB"))
        img = nd.array(img.astype(np.float32))
        if self._transform is not None:
            return self._transform(img, label)
        return img, label

    def __len__(self):
        return len(self.items)


class ImageRecordDataset(Dataset):
    """`.rec` image records -> (image NDArray HWC, label) samples
    (reference: python/mxnet/gluon/data/vision/datasets.py
    ImageRecordDataset). Each record is an IRHeader + encoded image; the
    header's label (scalar or vector) rides along. Decode is host-side
    (PIL), feeding numpy/NDArray batches to the chip via DataLoader."""

    def __init__(self, filename, flag=1, transform=None):
        self._record = RecordFileDataset(filename)
        self._flag = flag
        self._transform = transform

    def __getitem__(self, idx):
        header, img = unpack_img(self._record[idx], iscolor=self._flag)
        label = header.label
        if isinstance(label, np.ndarray) and label.size == 1:
            label = float(label[0])
        img = nd.array(img.astype(np.float32))
        if self._transform is not None:
            return self._transform(img, label)
        return img, label

    def __len__(self):
        return len(self._record)
