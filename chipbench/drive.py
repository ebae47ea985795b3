"""Builds the system under test from a configuration file and feeds it a mix.

Everything here goes through the service's public entry points:
``AsyncServer.push`` (FedBuff in the TEE), ``AsyncServer.encode_push`` +
``push_encoded`` + ``flush`` (a SecAgg+ round whose clients encode on the
chip), and ``ShardedAsyncServer.push`` + ``flush`` (the two-level tier).
A driver runs one session to its release and returns what the reference
needs to check it: the weight of each pool entry, the contributions folded,
the release's rng, and the params before and after.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import traffic


@dataclass
class Release:
    """One released model version, as the harness saw it."""

    due: float  # host clock: the session's last arrival handed over
    ready: float  # host clock: released params ready on the device
    contributions: int
    absent: int  # assigned session slots whose upload never came
    weights: np.ndarray  # (pool,) summed staleness weight per pool entry
    total_weight: float
    rng: Any  # the release's rng (central noise)
    before: Any = field(repr=False, default=None)
    after: Any = field(repr=False, default=None)


def make_params(config: dict, seed: int):
    """Model params from the seed, on the device, in one jitted call.

    ``config["model"]`` names a registry architecture (``arch``,
    ``reduced``), or, for the CPU tests, ``shapes``: a dict of leaf shapes
    of a small synthetic pytree."""
    m = config["model"]
    key = jnp.asarray(traffic.seed_words(seed), jnp.uint32)
    if "shapes" in m:
        shapes = {k: tuple(v) for k, v in m["shapes"].items()}
        return jax.jit(lambda k: {
            name: 0.02 * jax.random.normal(jax.random.fold_in(k, i), shp)
            for i, (name, shp) in enumerate(sorted(shapes.items()))})(key)
    from repro.configs import registry
    from repro.models.model import build_model
    model = build_model(registry.get_config(m["arch"],
                                            reduced=bool(m["reduced"])))
    return jax.jit(model.init)(key)


def make_pool(params, mix: dict, seed: int):
    """The delta pool, on the device, in one jitted call from the seed.

    ``mix["pool"]["size"]`` Gaussian delta pytrees shaped like ``params``,
    each scaled to a norm ``median * exp(sigma * N(0, 1))`` (relative to the
    clip norm: a lognormal spread around it, so clipping is live).  Returned
    as ``size / g`` stacked groups of ``g = stack_rows(mix)`` rows, or as
    ``size`` single pytrees when ``g`` is 1.
    """
    pool, group = mix["pool"], stack_rows(mix)
    size = int(pool["size"])
    if size % group:
        raise ValueError(f"pool size {size} is not a multiple of the "
                         f"group {group}")
    norms = float(pool["median"]) * np.exp(
        float(pool["sigma"]) * traffic.rng_for(seed, 5).standard_normal(size))
    leaves, treedef = jax.tree.flatten(params)
    sizes = [int(x.size) for x in leaves]
    offsets = np.cumsum([0] + sizes)
    key = jnp.asarray(traffic.seed_words(seed, 3)[1:], jnp.uint32)

    @jax.jit
    def build(key, norms):
        # one (size, d) draw, scaled row by row, cut into leaves
        flat = jax.random.normal(key, (size, int(offsets[-1])), jnp.float32)
        flat = flat * (norms / jnp.linalg.norm(flat, axis=1))[:, None]
        out = []
        for g in range(size // group):
            rows = flat[g * group:(g + 1) * group]
            tree = [rows[:, o:o + n].reshape((group,) + x.shape)
                    for o, n, x in zip(offsets, sizes, leaves)]
            if group == 1:
                tree = [x[0] for x in tree]
            out.append(jax.tree.unflatten(treedef, tree))
        return tuple(out)

    return build(key, jnp.asarray(norms, jnp.float32))


def stack_rows(mix: dict) -> int:
    """Rows per stacked delta a ``push`` takes: the mix's ``group`` for a
    backlog pushed in stacked groups, else 1 (round clients each encode
    their own delta)."""
    return int(mix.get("group", 1)) if mix["kind"] == "backlog" else 1


def pool_entries(pool, group: int):
    """The pool as single pytrees (for the reference), in entry order."""
    if group == 1:
        return list(pool)
    return [jax.tree.map(lambda x, r=r: x[r], g)
            for g in pool for r in range(group)]


def build_engine(config: dict, params, telemetry):
    from repro.configs.base import FLConfig
    from repro.core.fl.async_fl import AsyncServer
    from repro.core.fl.hierarchy import ShardedAsyncServer
    engines = {"AsyncServer": AsyncServer,
               "ShardedAsyncServer": ShardedAsyncServer}
    cls = engines[config["engine"]]
    return cls(params, FLConfig(**config["fl"]), telemetry=telemetry,
               **config["engine_args"])


def staleness_weight(s) -> np.ndarray:
    """FedBuff's polynomial staleness weight (1 + s) ** -0.5, as the
    reference computes it (float64 on the host, rounded once to f32)."""
    return (1.0 + np.asarray(s, np.float64)) ** -0.5


class Driver:
    """Feeds one engine the arrivals of one mix, a session at a time."""

    def __init__(self, engine, pool, mix: dict, seed: int):
        self.eng, self.pool, self.mix = engine, pool, mix
        self.group = int(mix.get("group", 1))
        self.size = int(mix["pool"]["size"])
        self._keys = traffic.rng_for(seed, 6)

    def _noise_key(self):
        return jnp.asarray(self._keys.integers(0, 1 << 32, 2,
                                               dtype=np.uint64)
                           .astype(np.uint32))

    def next_absent(self) -> int:
        """Assigned slots of the next session whose upload will not come."""
        return 0

    def session(self, keep: bool = False) -> Release:
        raise NotImplementedError


class BacklogDriver(Driver):
    """A queue that never empties: ``group`` arrivals per ``push``, the
    session releasing on its own when its buffer is full."""

    def __init__(self, engine, pool, mix, seed, *, max_arrivals=1 << 20):
        super().__init__(engine, pool, mix, seed)
        self.stal = traffic.staleness(mix, seed, max_arrivals)
        self.next = 0

    def session(self, keep: bool = False) -> Release:
        eng, g = self.eng, self.group
        v0, before = eng.version, eng.params
        rng = self._noise_key()
        weights = np.zeros(self.size)
        n, due = 0, 0.0
        while eng.version == v0:
            i = self.next
            self.next += g
            s = self.stal[i:i + g]
            if g == 1:
                delta, cv = self.pool[i % self.size], eng.version - int(s[0])
            else:
                delta = self.pool[(i // g) % (self.size // g)]
                cv = eng.version - s
            for r in range(g):
                weights[(i + r) % self.size] += staleness_weight(s[r])
            n += g
            due = time.perf_counter()
            eng.push(delta, cv, rng)
        jax.block_until_ready(eng.params)
        ready = time.perf_counter()
        return Release(due, ready, n, 0, weights, float(weights.sum()), rng,
                       before if keep else None,
                       eng.params if keep else None)


class RoundsDriver(Driver):
    """Back-to-back synchronous rounds: every slot is assigned a client,
    each client encodes on the chip (``encode_push``), the survivors'
    uploads are stored ``group`` at a time (``push_encoded``), and at the
    deadline a forced flush recovers the absent slots."""

    def __init__(self, engine, pool, mix, seed, *, max_rounds=1 << 14):
        super().__init__(engine, pool, mix, seed)
        self.absent = traffic.absent_slots(mix, seed, engine.buffer_size,
                                           max_rounds)
        self.round = 0

    def next_absent(self) -> int:
        return len(self.absent[self.round])

    def session(self, keep: bool = False,
                absent: Optional[List[int]] = None) -> Release:
        eng, g = self.eng, self.group
        if absent is None:
            absent = self.absent[self.round]
            self.round += 1
        gone = set(absent)
        v0, before = eng.version, eng.params
        rng = self._noise_key()
        groups = eng.buffer_size // g
        last = max(k for k in range(groups)
                   if any(s not in gone for s in range(k * g, k * g + g)))
        weights = np.zeros(self.size)
        n, due = 0, 0.0
        for k in range(groups):
            slots = range(k * g, k * g + g)
            # each client encodes its own delta (its pool entry) against
            # its assigned slot; the absent ones' uploads never arrive
            cps = [eng.encode_push(self.pool[s % self.size], eng.version,
                                   slot=s) for s in slots]
            kept = [cp for cp in cps if cp.slot not in gone]
            for cp in kept:
                weights[cp.slot % self.size] += 1.0
            n += len(kept)
            if k == last:
                due = time.perf_counter()
            if kept:
                eng.push_encoded(kept, rng)
        if eng.version == v0:
            eng.flush(rng=rng, force=True)
        jax.block_until_ready(eng.params)
        ready = time.perf_counter()
        return Release(due, ready, n, len(gone), weights,
                       float(weights.sum()), rng,
                       before if keep else None,
                       eng.params if keep else None)


def make_driver(engine, pool, mix: dict, seed: int) -> Driver:
    kind = mix["kind"]
    if kind == "backlog":
        return BacklogDriver(engine, pool, mix, seed)
    if kind == "rounds":
        return RoundsDriver(engine, pool, mix, seed)
    raise ValueError(f"traffic kind {kind!r}")


def warm_up(driver: Driver) -> int:
    """Drive every program the window will run once (its first call
    compiles, or loads from the persistent cache).  Returns the sessions
    driven."""
    if isinstance(driver, RoundsDriver):
        driver.session(absent=[])
        if float(driver.mix.get("dropout", 0.0)) > 0.0:
            driver.session(absent=[driver.eng.buffer_size - 1])
            return 2
        return 1
    driver.session()
    driver.session()
    return 2
