"""The synthetic CIFAR-like data every cell trains on, made from the seed.

A copy of the generator the program's `Session` uses for CNN cells, so
that the reference reads the same images and labels without taking any
array from the program.  Low-frequency class templates, shifted,
brightened and noised per sample.
"""
from __future__ import annotations

import numpy as np


def cifar_like(n_classes: int, n_train: int, n_test: int, image_size: int,
               seed: int):
    rng = np.random.default_rng(seed)
    freq = 4
    base = rng.standard_normal((n_classes, freq, freq, 3))
    templates = np.stack([
        np.kron(base[c], np.ones((image_size // freq, image_size // freq, 1)))
        for c in range(n_classes)])
    templates = templates / np.abs(templates).max()

    def sample(n):
        labels = rng.integers(0, n_classes, n)
        imgs = templates[labels].copy()
        shifts = rng.integers(-3, 4, (n, 2))
        for i in range(n):
            imgs[i] = np.roll(imgs[i], shifts[i], axis=(0, 1))
        imgs += rng.normal(0, 0.35, imgs.shape)
        imgs *= rng.uniform(0.8, 1.2, (n, 1, 1, 1))
        return imgs.astype(np.float32), labels.astype(np.int32)

    xtr, ytr = sample(n_train)
    xte, yte = sample(n_test)
    return (xtr, ytr), (xte, yte)
