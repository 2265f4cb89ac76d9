"""MNIST-class models (BASELINE configs 3-4) and synthetic data.

Data is generated, not downloaded — the deployment targets are zero-egress
TPU VMs, and what is measured is the framework and the compute, not
dataset IO.  ``synthetic_mnist`` produces a deterministic, learnable
class-conditional image distribution so "loss goes down" is a meaningful
assertion in tests.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


class MLP(nn.Module):
    """Flax MLP — the north star's "Flax MLP on MNIST" electron body."""

    features: tuple[int, ...] = (256, 128)
    num_classes: int = 10

    @nn.compact
    def __call__(self, x):
        x = x.reshape((x.shape[0], -1))
        for width in self.features:
            x = nn.relu(nn.Dense(width)(x))
        return nn.Dense(self.num_classes)(x)


class MnistCNN(nn.Module):
    """Small convnet for 28×28 inputs (BASELINE config 4)."""

    num_classes: int = 10

    @nn.compact
    def __call__(self, x):
        x = nn.Conv(32, (3, 3))(x)
        x = nn.relu(x)
        x = nn.avg_pool(x, (2, 2), strides=(2, 2))
        x = nn.Conv(64, (3, 3))(x)
        x = nn.relu(x)
        x = nn.avg_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(256)(x))
        return nn.Dense(self.num_classes)(x)


def synthetic_mnist(
    batch_size: int, *, seed: int = 0, flat: bool = False
) -> dict[str, np.ndarray]:
    """Class-conditional 28×28 images: each class is a distinct low-frequency
    template plus noise, so small models separate them quickly."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 10, size=(batch_size,))
    yy, xx = np.mgrid[0:28, 0:28].astype(np.float32) / 28.0
    templates = np.stack(
        [
            np.sin(2 * np.pi * (xx * (1 + c % 5) + yy * (1 + c // 5)) + c)
            for c in range(10)
        ]
    )
    images = templates[labels] + 0.3 * rng.standard_normal((batch_size, 28, 28)).astype(
        np.float32
    )
    images = images.astype(np.float32)[..., None]  # NHWC
    if flat:
        images = images.reshape(batch_size, -1)
    return {"image": images, "label": labels.astype(np.int32)}
