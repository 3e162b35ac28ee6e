"""Shared substrate for all federated strategies.

A ``Task`` bundles the model family used in the FL simulation (the paper's
backbones or the fast small CNN) with jitted loss/grad/eval functions and the
per-layer analytic FLOPs map used by the accounting.

``local_sgd`` runs the paper's local phase: E epochs of minibatch SGD with
fixed batch size (epochs are padded to whole batches so a single jitted step
serves all clients), optional DisPFL-style gradient masking.
``stacked_local_phase`` is the same phase for every client at once over a
padded ``stacked_schedule``: RoundEngine's vmap path and ScaleEngine's round
program both run it.

Determinism: callers must pass a *per-client, per-round* generator (see
``repro.fl.engine.derive_rng``) — never one generator shared across clients,
which would make results depend on client iteration order and break the
engine's vmap/parallel execution paths.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import cnn as cnn_mod
from repro.models.common import softmax_xent
from repro.optim import SGDConfig, init_sgd, masked_sgd_step, sgd_step

PyTree = Any


@dataclasses.dataclass
class Task:
    name: str
    init_fn: Callable[[jax.Array], PyTree]
    apply_fn: Callable[[PyTree, jax.Array], jax.Array]
    fwd_flops: dict[str, float]          # per-sample forward FLOPs per weight leaf
    n_classes: int

    def __post_init__(self):
        def loss(params, x, y):
            return softmax_xent(self.apply_fn(params, x), y)

        self._vg = jax.jit(jax.value_and_grad(loss))
        self._acc = jax.jit(
            lambda params, x, y: jnp.mean(
                (jnp.argmax(self.apply_fn(params, x), -1) == y)))

        def acc_one(params, x, y, live):
            correct = ((jnp.argmax(self.apply_fn(params, x), -1) == y)
                       & live).astype(jnp.float32)
            # sum * (1/n), not sum / n: XLA strength-reduces _acc's
            # divide-by-constant into a reciprocal multiply, and the
            # stacked eval must round identically to stay bit-equal to
            # the per-client loop
            n = jnp.sum(live.astype(jnp.float32))
            return jnp.sum(correct) * (jnp.float32(1.0) / n)

        def acc_stacked(params, x, y, live):
            # names the launch's ops ``eval`` in a device trace, beside the
            # round program's ``mix``/``local``/``evolve``
            with jax.named_scope("eval"):
                return jax.vmap(acc_one)(params, x, y, live)

        self._acc_stacked = jax.jit(acc_stacked)

    def value_and_grad(self, params, x, y):
        return self._vg(params, jnp.asarray(x), jnp.asarray(y))

    def accuracy(self, params, x, y) -> float:
        return float(self._acc(params, jnp.asarray(x), jnp.asarray(y)))


def make_cnn_task(kind: str = "smallcnn", n_classes: int = 10, hw: int = 16,
                  width: int = 16) -> Task:
    if kind == "smallcnn":
        return Task(
            name="smallcnn",
            init_fn=lambda key: cnn_mod.init_smallcnn(key, n_classes, width=width),
            apply_fn=cnn_mod.smallcnn_apply,
            fwd_flops=cnn_mod.smallcnn_fwd_flops(n_classes, hw, width),
            n_classes=n_classes)
    if kind == "resnet18":
        return Task(
            name="resnet18",
            init_fn=lambda key: cnn_mod.init_resnet18(key, n_classes),
            apply_fn=cnn_mod.resnet18_apply,
            fwd_flops=cnn_mod.resnet18_fwd_flops(n_classes, hw),
            n_classes=n_classes)
    if kind == "vgg11":
        return Task(
            name="vgg11",
            init_fn=lambda key: cnn_mod.init_vgg11(key, n_classes),
            apply_fn=cnn_mod.vgg11_apply,
            fwd_flops=cnn_mod.vgg11_fwd_flops(n_classes, hw),
            n_classes=n_classes)
    raise ValueError(kind)


@dataclasses.dataclass
class FLConfig:
    n_clients: int = 10
    rounds: int = 20
    local_epochs: int = 5
    batch_size: int = 32
    lr0: float = 0.1
    lr_decay: float = 0.998
    weight_decay: float = 5e-4
    momentum: float = 0.0
    topology: str = "random"            # random | ring | fc
    degree: int = 10
    seed: int = 0
    drop_prob: float = 0.0
    # sparsity (DisPFL / SubFedAvg)
    density: float = 0.5
    capacities: Optional[list[float]] = None   # per-client densities
    alpha0: float = 0.5                  # initial prune rate (cosine annealed)
    # dispfl_anneal: end-of-run density of the DA-DPFL-style cosine
    # sparse-to-sparser schedule (None -> density / 4)
    density_final: Optional[float] = None
    # Ditto / FOMO / fine-tuning
    prox_lambda: float = 0.75
    ft_epochs: int = 2
    eval_every: int = 1

    def lr_at(self, r: int) -> float:
        return self.lr0 * (self.lr_decay ** r)

    def client_density(self, k: int) -> float:
        if self.capacities is not None:
            return self.capacities[k]
        return self.density


@dataclasses.dataclass
class FLResult:
    acc_history: list[float]             # mean personalized test acc per eval
    final_accs: list[float]
    comm_busiest_mb: float               # per round
    comm_rows: dict
    flops_per_round: float               # per client
    flops_rows: dict
    rounds_to: dict[float, int] = dataclasses.field(default_factory=dict)

    @property
    def final_acc(self) -> float:
        return float(np.mean(self.final_accs))


def _pad_order(n: int, bs: int, rng: np.random.Generator) -> np.ndarray:
    order = rng.permutation(n)
    pad = (-len(order)) % bs
    if pad:
        order = np.concatenate([order, order[:pad]])
    return order


def stacked_schedule(clients, rngs, epochs: int,
                     bs: int) -> tuple[np.ndarray, np.ndarray]:
    """The stacked local phase's padded batch schedule for ``clients``.

    Client i draws ``epochs`` permutations from ``rngs[i]`` exactly as
    ``local_sgd`` does; ragged schedules are padded to the longest with
    recycled batches.  Returns int32 row indices ``(A, S, bs)`` into each
    client's training set and live flags ``(A, S)``: a padded step is not
    live, and ``stacked_local_phase`` makes it a no-op.
    """
    orders = [np.concatenate([_pad_order(c.n_train, bs, rng)
                              for _ in range(epochs)])
              for c, rng in zip(clients, rngs)]
    s_max = max(len(o) // bs for o in orders)
    idx = np.stack([np.resize(o, s_max * bs) for o in orders]).astype(
        np.int32)
    live = np.arange(s_max) < np.array([[len(o) // bs] for o in orders])
    return idx.reshape(-1, s_max, bs), live


def local_sgd(
    task: Task,
    params: PyTree,
    x: np.ndarray,
    y: np.ndarray,
    epochs: int,
    batch_size: int,
    lr: float,
    opt: SGDConfig,
    rng: np.random.Generator,
    mask: Optional[PyTree] = None,
) -> PyTree:
    """The paper's local phase (Alg. 1 lines 9-13)."""
    state = init_sgd(params, opt)
    bs = min(batch_size, len(y))
    for _ in range(epochs):
        order = _pad_order(len(y), bs, rng)
        for i in range(0, len(order), bs):
            sel = order[i: i + bs]
            _, grads = task.value_and_grad(params, x[sel], y[sel])
            if mask is not None:
                params, state = masked_sgd_step(params, grads, mask, state, opt, lr)
            else:
                params, state = sgd_step(params, grads, state, opt, lr)
    return params


def stacked_local_phase(apply_fn: Callable, opt: SGDConfig, params: PyTree,
                        masks: Optional[PyTree], bx: jax.Array, by: jax.Array,
                        live: jax.Array, lr: jax.Array) -> PyTree:
    """``local_sgd`` for every client at once, as a traceable function.

    A vmap over the stacked client dim of a lax.scan over the padded step
    schedule (``stacked_schedule``): masked/unmasked SGD steps from
    ``repro.optim``, padded (non-live) steps are exact no-ops, momentum is
    zero-initialized stacked per-client state.  ``masks`` is None for
    unmasked strategies.  RoundEngine's vmap path jits it alone;
    ScaleEngine traces it into its round program.
    """

    def loss(p, x, y):
        return softmax_xent(apply_fn(p, x), y)

    grad = jax.grad(loss)
    use_mask = masks is not None

    def per_client(p, m, cx, cy, lv):
        def body(carry, xyl):
            w, st = carry
            x, y, alive = xyl
            g = grad(w, x, y)
            if use_mask:
                w2, st2 = masked_sgd_step(w, g, m, st, opt, lr)
            else:
                w2, st2 = sgd_step(w, g, st, opt, lr)
            # jnp.where keeps a live step bit-identical to the plain step
            w = jax.tree.map(lambda o, nn: jnp.where(alive, nn, o), w, w2)
            st = jax.tree.map(lambda o, nn: jnp.where(alive, nn, o), st, st2)
            return (w, st), None

        st0 = ({"mu": jax.tree.map(jnp.zeros_like, p)}
               if opt.momentum != 0.0 else {})
        (p, _), _ = jax.lax.scan(body, (p, st0), (cx, cy, lv))
        return p

    if use_mask:
        return jax.vmap(per_client)(params, masks, bx, by, live)
    return jax.vmap(
        lambda p, cx, cy, lv: per_client(p, None, cx, cy, lv))(
            params, bx, by, live)


def finetune_clients(
    task: Task,
    params: list[PyTree],
    clients,
    epochs: int,
    batch_size: int,
    lr: float,
    opt: SGDConfig,
    rng_for: Callable[[int], np.random.Generator],
    mask=None,
) -> list[PyTree]:
    """Fine-tune every client from ``params[k]`` (the -FT eval variants).

    ``rng_for(k)`` supplies the per-client generator; ``mask`` may be a
    single shared mask tree, a per-client list, or None.
    """
    out = []
    for k, c in enumerate(clients):
        m = mask[k] if isinstance(mask, list) else mask
        out.append(local_sgd(task, params[k], c.train_x, c.train_y, epochs,
                             batch_size, lr, opt, rng_for(k), mask=m))
    return out


def evaluate_clients(task: Task, client_params: list[PyTree], clients) -> list[float]:
    return [
        task.accuracy(p, c.test_x, c.test_y)
        for p, c in zip(client_params, clients)
    ]


def stack_eval_arrays(clients) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pad the K ragged test sets to one (K, L, ...) batch for stacked eval.

    Padding wraps each client's own test set (so padded rows are valid
    inputs, never zeros) and a (K, L) ``live`` mask marks the real rows.
    Build once and reuse — these arrays are round-invariant.
    """
    L = max(len(c.test_y) for c in clients)
    xs, ys, lives = [], [], []
    for c in clients:
        n = len(c.test_y)
        idx = np.resize(np.arange(n), L)
        xs.append(c.test_x[idx])
        ys.append(c.test_y[idx])
        lives.append(np.arange(L) < n)
    return (jnp.asarray(np.stack(xs)), jnp.asarray(np.stack(ys)),
            jnp.asarray(np.stack(lives)))


def evaluate_clients_stacked(task: Task, stacked_params: PyTree, clients,
                             arrays=None) -> list[float]:
    """One vmapped launch replacing the per-client host eval loop.

    Per client this computes ``sum(correct ∧ live) / sum(live)`` — the live
    count is exactly ``len(test_y)`` and 0/1 sums are exact in fp32, so the
    result matches ``evaluate_clients`` bit for bit (golden-tested in
    tests/test_scale_engine.py).  ``arrays`` is an optional pre-built
    ``stack_eval_arrays(clients)`` to amortize the padding across rounds.
    """
    if arrays is None:
        arrays = stack_eval_arrays(clients)
    x, y, live = arrays
    accs = task._acc_stacked(stacked_params, x, y, live)
    return [float(a) for a in accs]


def rounds_to_targets(history: list[float], targets: list[float]) -> dict[float, int]:
    out = {}
    for t in targets:
        hit = next((i + 1 for i, a in enumerate(history) if a >= t), -1)
        out[t] = hit
    return out
