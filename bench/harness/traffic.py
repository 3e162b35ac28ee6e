"""The general generator that turns a train traffic file and a seed into
clients' data.

Every seed gives the same sizes, so that seeds change which data, not how
much work: ``image_clients`` makes a label-skewed (pathological)
CIFAR-shaped split.  Client slots take classes in a fixed round-robin, so
the per-client sizes are the same for every seed; the seed permutes the
class labels, which client gets which pair, the class templates and the
pixels.
"""
from __future__ import annotations

import numpy as np


def _rng(seed: int, tag: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), tag]))


def _template(rng, hw: int, c: int) -> np.ndarray:
    """A smooth random image in [-1, 1]: a 4x4 grid upsampled bilinearly."""
    base = rng.normal(size=(4, 4, c))
    pos = np.linspace(0, 3, hw)
    i0 = np.floor(pos).astype(int)
    i1 = np.minimum(i0 + 1, 3)
    f = (pos - i0)[:, None, None]
    rows = base[i0] * (1 - f) + base[i1] * f
    out = rows[:, i0] * (1 - f.transpose(1, 0, 2)) + rows[:, i1] * f.transpose(1, 0, 2)
    return out / (np.abs(out).max() + 1e-8)


def image_clients(seed: int, n_clients: int, n_classes: int,
                  samples_per_class: int, classes_per_client: int, hw: int,
                  channels: int, noise: float, test_per_client: int) -> list[dict]:
    """One dict per client: ``train_x`` (n, hw, hw, c) float32, ``train_y``
    int32, ``test_x``, ``test_y``, ``label_dist``."""
    rng = _rng(seed, 0xDA7A)
    relabel = rng.permutation(n_classes)
    owner = rng.permutation(n_clients)
    templates = np.stack([_template(rng, hw, channels)
                          for _ in range(n_classes)]).astype(np.float32)
    slots = np.arange(n_clients * classes_per_client)
    holders = {c: [] for c in range(n_classes)}
    for s in slots:
        holders[int(relabel[s % n_classes])].append(int(owner[s // classes_per_client]))
    shares = {k: [] for k in range(n_clients)}
    for c, ks in holders.items():
        for k, n in zip(ks, [len(a) for a in np.array_split(
                np.arange(samples_per_class), len(ks))] if ks else []):
            shares[k].append((c, n))
    out = []
    for k in range(n_clients):
        ys = np.concatenate([np.full(n, c, np.int32) for c, n in shares[k]])
        ys = ys[rng.permutation(len(ys))]
        xs = templates[ys] + noise * rng.standard_normal(
            (len(ys), hw, hw, channels), dtype=np.float32)
        test_y = ys[:test_per_client].copy()
        test_x = templates[test_y] + noise * rng.standard_normal(
            (len(test_y), hw, hw, channels), dtype=np.float32)
        dist = np.bincount(ys, minlength=n_classes) / len(ys)
        out.append({"train_x": xs, "train_y": ys, "test_x": test_x,
                    "test_y": test_y, "label_dist": dist})
    return out

