"""The one generator of every traffic mix: tokens for the measured path and
the stream of predictor queries, both drawn from ``--seed`` and the mix's
data file (``bench/traffic/<mix>.json``).

A query stream is ``{"endpoint": ..., "count": n, <dimension>: [low,
high], ...}``: ``n`` distinct points, each dimension drawn uniformly from
its closed range (``"<dim>_multiple"`` keeps only multiples of that step;
``"layouts"`` lists the (dp, tp) pairs to draw from).  The set of points is
the same for every seed and only their order follows the seed, so every run
asks the same questions; none equals the cell's own point, so the
predictor's cache never answers.
"""
from __future__ import annotations

import numpy as np

from common import seed_words

_DIMS = ("batch", "seq")


def rng(seed: int, stream: str) -> np.random.Generator:
    """A numpy generator for one named use of the seed."""
    lo, hi = seed_words(seed)
    return np.random.default_rng([lo, hi, sum(map(ord, stream))])


def _draw(spec: dict, dim: str, r: np.random.Generator) -> int:
    low, high = spec[dim]
    step = spec.get(f"{dim}_multiple", 1)
    return int(step * r.integers(-(-low // step), high // step + 1))


def query_points(spec: dict, seed: int, exclude=()) -> list[dict]:
    """The stream's points, in the order ``seed`` gives them."""
    r = np.random.default_rng(sum(map(ord, spec["endpoint"])))
    dims = [d for d in _DIMS if d in spec]
    layouts = [tuple(x) for x in spec.get("layouts", [(1, 1)])]
    seen = {tuple(sorted(p.items())) for p in exclude}
    points = []
    while len(points) < spec["count"]:
        p = {d: _draw(spec, d, r) for d in dims}
        if "layouts" in spec:
            p["dp"], p["tp"] = layouts[int(r.integers(len(layouts)))]
        key = tuple(sorted(p.items()))
        if key not in seen:
            seen.add(key)
            points.append(p)
    order = rng(seed, "queries").permutation(len(points))
    return [points[i] for i in order]


def own_point(mix: dict) -> dict:
    """The point of the stream that the cell's own step is, as far as the
    mix states it: the harness asks it in set-up, so the stream skips it."""
    spec = mix["queries"]
    point = {d: mix[d] for d in _DIMS if d in spec and d in mix}
    if "layouts" in spec:
        point.update(dp=1, tp=1)
    return point


def ask(svc, cfg, spec: dict, point: dict, dtype: str) -> float:
    """One query to the predictor; returns its answer in seconds."""
    endpoint = spec["endpoint"]
    if endpoint == "latency_train":
        return svc.latency_train(cfg, point["batch"], point["seq"],
                                 dp=point["dp"], tp=point["tp"],
                                 dtype=dtype).seconds
    if endpoint == "latency_query":
        return svc.latency_query(cfg, point["batch"], point["seq"],
                                 dtype=dtype).seconds
    raise ValueError(f"unknown query endpoint {endpoint!r}")


def token_block(seed: int, stream: str, shape, vocab: int):
    """int32 token ids of ``shape``, drawn on the device from the seed."""
    import jax
    import jax.numpy as jnp
    lo, hi = seed_words(seed)

    def draw(lo, hi):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.key(sum(map(ord, stream))), lo), hi)
        return jax.random.randint(key, shape, 0, vocab, jnp.int32)

    return jax.jit(draw)(jnp.uint32(lo), jnp.uint32(hi))
