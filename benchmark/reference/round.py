"""The federated round in plain terms, shared by every configuration.

One round, W clients: each client takes the gradient of its own batch's mean
loss at the current parameters, adds weight decay, and clips it to
``max_grad_norm``; the server takes the mean over clients and applies the
algebra the traffic mix names (``reference.server``, ``"module:name"``):

- ``DenseServer`` (uncompressed): ``m = rho m + g``; ``p -= lr m``;
- ``SketchServer`` (FetchSGD, arXiv:2007.07682 Alg. 1, virtual error
  feedback): ``S(g)`` is the CountSketch of ``g``; ``m = rho m + S(g)``;
  ``e += lr m``; ``delta = top_k(unsketch(e))``; ``e -= S(delta)``;
  ``p -= delta``.

A mix with another algebra brings a class of its own, in a file of its own,
with ``__init__(d, spec)`` and ``step(g, lr) -> (delta, table or None)``;
``step`` may write ``delta`` into ``g``'s own buffer.

Client gradients run on the device through ``jax.grad`` of the model's plain
loss, one client at a time so that the float32 activations fit; the server's
algebra runs in numpy on the host (see ``sketch.py`` for why). Nothing here
imports the system under test.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import resolve
from benchmark.reference.sketch import Sketch, top_k_dense


class Algo(NamedTuple):
    """What the traffic mix and configuration state about the round."""

    lr: float
    weight_decay: float = 0.0
    max_grad_norm: Optional[float] = None
    server: str = "benchmark.reference.round:DenseServer"   # "module:name"
    spec: dict = {}                # the traffic file's ``reference`` group


class DenseServer:
    """``m = rho m + g``; ``p -= lr m`` (``rho`` may be 0: then there is no
    ``m``). ``m`` is updated in place and ``lr m`` written into ``g``."""

    def __init__(self, d, spec):
        self.rho = np.float32(spec.get("rho", 0.0))
        self.m = np.zeros(d, np.float32) if self.rho else None

    def step(self, g, lr):
        """``(what to take off the parameters, the table if there is one)``."""
        if self.m is not None:
            self.m *= self.rho
            self.m += g
        return np.multiply(g if self.m is None else self.m, lr, out=g), None


class SketchServer:
    """FetchSGD with virtual error feedback (module docstring)."""

    def __init__(self, d, spec):
        self.sk = Sketch(d, int(spec["cols"]), int(spec["rows"]), int(spec["hash_seed"]))
        self.rho, self.k = np.float32(spec["rho"]), int(spec["k"])
        self.m, self.e = self.sk.zeros(), self.sk.zeros()

    def step(self, g, lr):
        table = self.sk.sketch(g)
        self.m = self.rho * self.m + table
        self.e = self.e + lr * self.m
        delta = top_k_dense(self.sk.estimate(self.e), self.k)
        nz = np.flatnonzero(delta)
        self.e = self.e - self.sk.sketch_sparse(nz, delta[nz])
        return delta, table


class Trace(NamedTuple):
    """What three rounds leave behind, for the comparison."""

    losses: list          # mean client loss of each round, before its update
    grad1: np.ndarray     # round 1's mean clipped gradient, flat [D]
    table1: object        # its sketch (sketch mode), else None
    params: list          # flat parameters [after round 1 (or None), after the last]


def make_client_grad(loss_fn: Callable, unflatten: Callable, algo: Algo,
                     precision: str = "float32"):
    """``(p_flat, client_batch) -> (clipped flat gradient, loss)`` on the
    device. ``precision`` below float32 is for the controls."""

    def client(p_flat, batch):
        def f(pf):
            return loss_fn(unflatten(pf), batch, precision)

        loss, g = jax.value_and_grad(f)(p_flat)
        g = g + algo.weight_decay * p_flat
        if algo.max_grad_norm is not None:
            norm = jnp.sqrt(jnp.sum(jnp.square(g)))
            g = g * jnp.minimum(1.0, algo.max_grad_norm / (norm + 1e-12))
        return g, loss

    jitted = jax.jit(client)

    def run(p_flat, batch):
        with jax.default_matmul_precision("highest"):
            return jitted(p_flat, batch)

    return run


PIECE = 1 << 22  # elements a device-to-host copy


@partial(jax.jit, static_argnums=2)
def _piece(x, start, size):
    return jax.lax.dynamic_slice_in_dim(x, start, size)


def to_host(x) -> np.ndarray:
    """A flat device vector as a new host array, copied ``PIECE`` elements
    at a time, so that no second host buffer of its size is made."""
    out = np.empty(x.shape, np.float32)
    for s in range(0, x.shape[0], PIECE):
        n = min(PIECE, x.shape[0] - s)
        out[s:s + n] = np.asarray(_piece(x, s, n))
    return out


def run_rounds(client_grad: Callable, p0: np.ndarray, rounds: list, algo: Algo,
               on_round: Optional[Callable] = None, first: bool = True) -> Trace:
    """Follow ``rounds`` (a list of per-round batches ``{k: [W, B, ...]}``)
    from ``p0``; ``on_round(i)`` is called after round ``i`` (from 1).
    ``first``: keep the parameters after round 1 (a stand-in's ``p1``).

    Of ``[D]`` vectors the host holds the parameters (updated in place),
    round 1's gradient, the parameters after round 1 where kept, and the
    round's mean gradient, which the server's step may overwrite (besides a
    server's own state)."""
    p = np.array(p0, np.float32)
    d = p.size
    server = resolve(algo.server)(d, algo.spec)
    lr = np.float32(algo.lr)
    losses, params, grad1, table1 = [], [None], None, None
    for i, batch in enumerate(rounds, 1):
        W = next(iter(batch.values())).shape[0]
        p_dev = jnp.asarray(p)
        total, loss_sum = jnp.zeros(d, jnp.float32), 0.0
        for w in range(W):
            g, loss = client_grad(p_dev, {k: jnp.asarray(v[w]) for k, v in batch.items()})
            total = total + g
            loss_sum += float(loss)
        g = to_host(total)
        del total, p_dev
        g /= np.float32(W)
        losses.append(loss_sum / W)
        if i == 1:
            grad1 = g.copy()
        delta, table = server.step(g, lr)
        p -= delta
        del g, delta
        if i == 1:
            table1, params = table, [p.copy() if first else None]
        if on_round is not None:
            on_round(i)
    params.append(p)
    return Trace(losses, grad1, table1, params)
