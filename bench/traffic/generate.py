"""The benchmark's one traffic generator. Every traffic mix is a data file
beside this one (``<traffic>.json``); this module turns its parameters and
the run's ``--seed`` into inputs, on the device, with no [V, D] table.

A sample of class ``y`` is the unit direction drawn from ``(seed, y)`` plus
isotropic noise of norm about ``noise`` drawn from ``(seed, step, row)``,
so the classes are separable and each step's rows all differ. Labels are
uniform over the configuration's classes. Serving queries come from the
same sampler; their arrival times are an open-loop Poisson process drawn
from the seed on the host.

The same seed gives the same inputs: the references under
``bench/reference/`` call these functions to see what the program saw.
"""
from __future__ import annotations

import numpy as np


def seed31(seed: int) -> int:
    """``--seed`` may exceed 32 bits; JAX keys take the low 31."""
    return int(seed) % (2 ** 31)


def keys(seed: int):
    """(direction key, step key) of ``seed``, made outside any jit: the
    seed reaches the generator's programs as data, so every seed runs the
    same compiled programs."""
    import jax
    base = jax.random.PRNGKey(seed31(seed))
    return jax.random.fold_in(base, 1), jax.random.fold_in(base, 2)


def samples(ks, step, labels, d: int, noise: float):
    """Traceable: features [n, d] float32 for ``labels`` [n] at ``step``,
    from the keys ``ks`` of a seed."""
    import jax
    import jax.numpy as jnp

    kdir, kstep = ks
    dirs = jax.vmap(lambda y: jax.random.normal(
        jax.random.fold_in(kdir, y), (d,), jnp.float32))(labels)
    dirs = dirs / jnp.linalg.norm(dirs, axis=-1, keepdims=True)
    k = jax.random.fold_in(jax.random.fold_in(kstep, step), 1)
    n = jax.random.normal(k, (labels.shape[0], d), jnp.float32)
    return dirs + (noise / np.sqrt(d)) * n


def labels_at(ks, step, n: int, classes: int):
    """Traceable: uniform labels [n] int32 of ``step``."""
    import jax
    import jax.numpy as jnp

    k = jax.random.fold_in(jax.random.fold_in(ks[1], step), 0)
    return jax.random.randint(k, (n,), 0, classes, jnp.int32)


def train_batch_fn(seed: int, *, classes: int, d: int, batch: int,
                   noise: float, sharding=None):
    """``t -> {"features", "labels"}``: one global batch, made on the
    device by a jitted program (laid out by ``sharding`` when given)."""
    import jax

    ks = keys(seed)

    def make(t, ks):
        y = labels_at(ks, t, batch, classes)
        return {"features": samples(ks, t, y, d, noise), "labels": y}

    if sharding is None:
        made = jax.jit(make)
    else:
        made = jax.jit(make, out_shardings={"features": sharding,
                                            "labels": sharding})
    return lambda t: made(t, ks)


# serving queries live at steps past any training step
QUERY_STEP = 1 << 30


def queries(seed: int, n: int, *, classes: int, d: int, noise: float):
    """(features [n, d] float32 numpy, labels [n] int32 numpy): the unique
    queries of a serving run, made on the device in one call."""
    import jax

    @jax.jit
    def make(ks):
        y = labels_at(ks, QUERY_STEP, n, classes)
        return samples(ks, QUERY_STEP, y, d, noise), y

    f, y = jax.device_get(make(keys(seed)))
    return np.asarray(f), np.asarray(y)


def poisson_arrivals(seed: int, rate: float, seconds: float) -> np.ndarray:
    """Due times (s from the window's start) of an open-loop Poisson
    process at ``rate`` per second over ``seconds``, conditioned on its
    mean count: round(rate * seconds) points placed uniformly and sorted.
    Every seed then offers the same number of requests, in other gaps."""
    rng = np.random.default_rng([seed31(seed), 7])
    n = int(round(rate * seconds))
    return np.sort(rng.uniform(0.0, seconds, size=n))
