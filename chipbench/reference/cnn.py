"""Plain reference of split federated training of the CNN configurations.

Straightforward `jax.numpy`, one client at a time, at float32 with
`highest` matmul precision (on a TPU a float32 convolution otherwise
makes one bfloat16 pass).  It follows the HASFL round (Algorithm 1 of
arXiv:2506.08426):

- every client computes the gradient of its masked-mean cross entropy
  on its own rows, with the full model (split execution gives the same
  gradient, Sec. III);
- each client's gradient is clipped to global norm ``clip``;
- client-specific layers (those below the deepest cut) take their own
  SGD step (Eq. 5-6); server-common layers take the mean of the
  clients' SGD results every round (Eq. 4);
- every ``agg_interval`` rounds all layers take that mean (Eq. 7).

With ``dtype=bfloat16`` the same code is the control: weights, data,
activations and updates in bfloat16 at the default precision.
Nothing here imports the program.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.data import cifar_like


def data(cfg: Dict, traffic: Dict, seed: int):
    """``((train images, labels), (test images, labels))`` of the cell,
    made from the seed."""
    return cifar_like(cfg["n_classes"], traffic["n_train"],
                      traffic["n_test"], cfg["image_size"], seed)


def leaf_names(cfg: Dict) -> List[str]:
    """Names of the parameter leaves, ``<unit>.<w|b>`` and
    ``<unit>.proj.<w|b>`` for the residual projections."""
    names = []
    cin = cfg["in_channels"]
    for i, c in enumerate(cfg["conv_channels"]):
        names += [f"{i}.w", f"{i}.b"]
        if cfg["residual"] and i > 0 and cin != c:
            names += [f"{i}.proj.w", f"{i}.proj.b"]
        cin = c
    n_conv = len(cfg["conv_channels"])
    for j in range(len(cfg["fc_dims"]) + 1):
        names += [f"{n_conv + j}.w", f"{n_conv + j}.b"]
    return names


def n_units(cfg: Dict) -> int:
    return len(cfg["conv_channels"]) + len(cfg["fc_dims"]) + 1


def init_params(cfg: Dict, seed: int) -> Dict[str, jax.Array]:
    """The initial weights, from the seed: one key per layer from
    ``split(PRNGKey(seed), layers + 1)``, He-normal 3x3 kernels (a
    projection draws from ``fold_in(key, 7)``), dense layers normal over
    ``sqrt(fan_in)``, zero biases."""
    keys = jax.random.split(jax.random.PRNGKey(seed), n_units(cfg) + 1)
    p = {}
    cin = cfg["in_channels"]
    for i, c in enumerate(cfg["conv_channels"]):
        scale = np.sqrt(2.0 / (9 * cin))
        p[f"{i}.w"] = jax.random.normal(keys[i], (3, 3, cin, c)) * scale
        p[f"{i}.b"] = jnp.zeros((c,))
        if cfg["residual"] and i > 0 and cin != c:
            p[f"{i}.proj.w"] = jax.random.normal(
                jax.random.fold_in(keys[i], 7), (3, 3, cin, c)) * scale
            p[f"{i}.proj.b"] = jnp.zeros((c,))
        cin = c
    hw = cfg["image_size"]
    if cfg["residual"]:
        prev = cin
    else:
        hw = hw // 2 ** len(cfg["pool_after"])
        prev = cin * hw * hw
    idx = len(cfg["conv_channels"])
    for f in list(cfg["fc_dims"]) + [cfg["n_classes"]]:
        p[f"{idx}.w"] = jax.random.normal(keys[idx], (prev, f)) / np.sqrt(prev)
        p[f"{idx}.b"] = jnp.zeros((f,))
        prev = f
        idx += 1
    return p


def _conv(x, w, b, stride):
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b


def forward(p: Dict, x, cfg: Dict):
    """Logits of a batch of images ``[B, H, W, 3]``."""
    pools = set(cfg["pool_after"])
    cin = cfg["in_channels"]
    for i, c in enumerate(cfg["conv_channels"]):
        w, b = p[f"{i}.w"], p[f"{i}.b"]
        if cfg["residual"] and i > 0 and cin == c:
            x = jax.nn.relu(_conv(x, w, b, 1) + x)
        elif cfg["residual"] and i > 0:
            x = jax.nn.relu(_conv(x, w, b, 2)
                            + _conv(x, p[f"{i}.proj.w"], p[f"{i}.proj.b"], 2))
        else:
            x = jax.nn.relu(_conv(x, w, b, 1))
        if i + 1 in pools:
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        cin = c
    x = x.mean(axis=(1, 2)) if cfg["residual"] else x.reshape(x.shape[0], -1)
    idx = len(cfg["conv_channels"])
    for j in range(len(cfg["fc_dims"])):
        x = jax.nn.relu(x @ p[f"{idx + j}.w"] + p[f"{idx + j}.b"])
    last = idx + len(cfg["fc_dims"])
    return x @ p[f"{last}.w"] + p[f"{last}.b"]


def nll(p, x, y, mask, cfg):
    """Cross entropy averaged over the rows with ``mask`` 1."""
    logp = jax.nn.log_softmax(forward(p, x, cfg))
    per_row = -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
    return (per_row * mask).sum() / jnp.maximum(mask.sum(), 1)


def unit_of(name: str) -> int:
    return int(name.split(".")[0])


class Trainer:
    """Runs HASFL rounds for N clients held as ``[N, ...]`` leaves."""

    def __init__(self, cfg: Dict, init: Dict, n_clients: int, *, lr: float,
                 clip: float, agg_interval: int, dtype=jnp.float32,
                 precision: str = "highest"):
        self.cfg = cfg
        self.n = n_clients
        self.lr, self.clip, self.interval = lr, clip, agg_interval
        self.dtype = dtype
        self.precision = precision
        self.names = leaf_names(cfg)
        self.init = {k: v.astype(dtype) for k, v in init.items()}
        self.state = {k: jnp.broadcast_to(v[None], (n_clients,) + v.shape)
                      for k, v in self.init.items()}
        self.t = 0

        def client_step(state, i, images, labels, idx, mask):
            p = {k: v[i] for k, v in state.items()}
            x = jnp.take(images, idx, axis=0)
            x = jnp.where(mask[:, None, None, None] > 0, x, 0).astype(dtype)
            y = jnp.take(labels, idx, axis=0)
            loss, g = jax.value_and_grad(nll)(p, x, y, mask.astype(dtype), cfg)
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(v.astype(jnp.float32)))
                                for v in g.values()))
            scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
            new = {k: state[k].at[i].set(
                p[k] - (lr * scale * g[k].astype(jnp.float32)).astype(dtype))
                for k in state}
            gnorm = {k: jnp.sum(jnp.square(g[k].astype(jnp.float32)))
                     for k in g}
            return new, loss.astype(jnp.float32), gnorm

        def combine(state, client_specific):
            out = {}
            for k, v in state.items():
                mean = v.astype(jnp.float32).mean(axis=0).astype(dtype)
                out[k] = jnp.where(client_specific[unit_of(k)] > 0, v,
                                   jnp.broadcast_to(mean[None], v.shape))
            return out

        def evaluate(state, images, labels):
            p = {k: v.astype(jnp.float32).mean(axis=0).astype(dtype)
                 for k, v in state.items()}
            logits = forward(p, images.astype(dtype), cfg).astype(jnp.float32)
            logp = jax.nn.log_softmax(logits)
            loss = -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()
            return loss, (logits.argmax(-1) == labels).mean()

        def delta_norms(state, init):
            return {k: jnp.sqrt(jnp.sum(jnp.square(
                state[k].astype(jnp.float32)
                - init[k].astype(jnp.float32)[None]))) for k in state}

        self._client_step = jax.jit(client_step, donate_argnums=(0,))
        self._combine = jax.jit(combine, donate_argnums=(0,))
        self._evaluate = jax.jit(evaluate)
        self._delta = jax.jit(delta_norms)

    def round(self, images, labels, idx, counts, l_c: int):
        """One round.  ``idx`` [N, b_pad] sample indices, ``counts`` [N]
        real rows, ``l_c`` the number of client-specific layers.  Returns
        the clients' losses [N] and the squared gradient norm of each
        leaf summed over clients."""
        self.t += 1
        b_pad = idx.shape[1]
        losses = []
        gsq = {k: 0.0 for k in self.names}
        with jax.default_matmul_precision(self.precision):
            for i in range(self.n):
                mask = (np.arange(b_pad) < counts[i]).astype(np.float32)
                self.state, loss, g = self._client_step(
                    self.state, i, images, labels, jnp.asarray(idx[i]),
                    jnp.asarray(mask))
                losses.append(loss)
                gsq = {k: gsq[k] + g[k] for k in gsq}
            agg = self.t % self.interval == 0
            spec = np.zeros(n_units(self.cfg), np.float32)
            if not agg:
                spec[:l_c] = 1.0
            self.state = self._combine(self.state, jnp.asarray(spec))
        return (np.asarray(jnp.stack(losses)),
                {k: float(v) for k, v in gsq.items()})

    def evaluate(self, images, labels):
        with jax.default_matmul_precision(self.precision):
            loss, acc = self._evaluate(self.state, images, labels)
        return float(loss), float(acc)

    def delta_norms(self) -> Dict[str, float]:
        out = self._delta(self.state, self.init)
        return {k: float(v) for k, v in out.items()}
