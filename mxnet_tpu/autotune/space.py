"""Typed search spaces over the runtime's REAL knobs (docs/autotune.md).

A space is a small set of named :class:`Choice` axes plus a validity
predicate — the same contracts the runtime enforces, reused at
search time so the tuner can only propose configurations the runtime
would accept:

- Pallas block shapes must be ones the chip's compiler takes
  (``pallas.registry.block_ok``: last two dims multiples of (8, 128) or
  the whole array dim — the kernels clamp any other tuned block back to
  the default);
- bucket lattices must keep :meth:`BucketGrid.grid_bound` under the
  compile budget (the PR-4 bounded-compile guarantee);
- serving/router/decode scalars must stay in their documented ranges.

Stdlib-only: spaces are data + predicates, importable without jax.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Tuple

__all__ = ["Choice", "Space", "pallas_block_space",
           "serving_space", "router_space", "decode_space",
           "bucket_space"]


@dataclass(frozen=True)
class Choice:
    """One categorical axis: a finite, ordered value set."""

    name: str
    values: Tuple

    def __post_init__(self):
        if not self.values:
            raise ValueError(f"choice {self.name!r} has no values")


@dataclass
class Space:
    """A named product of :class:`Choice` axes with a validity
    predicate (``validate(config) -> None | reason``) and the built-in
    default configuration — the A/B baseline every search includes."""

    name: str
    params: Dict[str, Choice]
    default: Dict
    validate: Optional[Callable] = None
    # how a winning config lands in the tuned table:
    # (family, key) — e.g. ("serving", "window_ms") — or a callable for
    # structured families (pallas blocks); see runner.table_patch
    table_map: Dict[str, Tuple[str, str]] = field(default_factory=dict)

    def __post_init__(self):
        bad = sorted(set(self.default) - set(self.params))
        if bad:
            raise ValueError(f"space {self.name!r}: default names "
                             f"unknown params {bad}")

    def reason(self, config: Dict) -> Optional[str]:
        """Why ``config`` is invalid (None = valid)."""
        for name, value in config.items():
            ch = self.params.get(name)
            if ch is None:
                return f"unknown_param:{name}"
            if value not in ch.values:
                return f"out_of_domain:{name}={value!r}"
        if self.validate is not None:
            return self.validate(config)
        return None

    def sample(self, rng) -> Dict:
        """One valid configuration (rejection sampling, bounded — a
        space whose predicate rejects everything raises instead of
        spinning)."""
        for _ in range(256):
            cfg = {n: ch.values[rng.randrange(len(ch.values))]
                   for n, ch in self.params.items()}
            if self.reason(cfg) is None:
                return cfg
        raise ValueError(f"space {self.name!r}: no valid sample in 256 "
                         "draws — the validity predicate rejects the "
                         "whole domain")

    def neighbors(self, config: Dict, name: str):
        """All valid single-axis perturbations of ``config`` along
        ``name`` (coordinate descent's move set)."""
        out = []
        for v in self.params[name].values:
            if v == config.get(name):
                continue
            cand = dict(config)
            cand[name] = v
            if self.reason(cand) is None:
                out.append(cand)
        return out

    def grid(self):
        """Every valid configuration (small spaces only — used by
        successive halving's rung-0 seeding when the domain is tiny)."""
        names = sorted(self.params)
        for combo in itertools.product(
                *(self.params[n].values for n in names)):
            cfg = dict(zip(names, combo))
            if self.reason(cfg) is None:
                yield cfg


# ---------------------------------------------------------------------------
# concrete spaces
# ---------------------------------------------------------------------------
def pallas_block_space(kernel: str, r: int, c: int) -> Space:
    """Block-shape space for one epilogue kernel at one (r, c) shape
    class.  Validity = the kernel's own contract (``block_ok``): each
    block axis is a multiple of its alignment (8 rows, 128 lanes) up to
    the built-in cap, or the whole dim."""
    from ..pallas.registry import BLOCK_CAPS, block_ok, default_block
    r, c = int(r), int(c)
    row_cap, col_cap = BLOCK_CAPS
    rows = tuple(range(8, min(r, row_cap) + 1, 8)) + \
        ((r,) if r <= row_cap and r % 8 else ())
    cols = tuple(range(128, min(c, col_cap) + 1, 128)) + \
        ((c,) if c <= col_cap and c % 128 else ())
    br0, bc0 = default_block(r, c)

    def validate(cfg):
        br, bc = cfg["block_r"], cfg["block_c"]
        if not block_ok(r, c, br, bc):
            return f"block_not_aligned:{br}x{bc}_vs_{r}x{c}"
        return None

    return Space(
        name=f"pallas:{kernel}:{r}x{c}",
        params={"block_r": Choice("block_r", rows),
                "block_c": Choice("block_c", cols)},
        default={"block_r": br0, "block_c": bc0},
        validate=validate,
        table_map={"block_r": ("pallas", f"{kernel}.{r}x{c}.block_r"),
                   "block_c": ("pallas", f"{kernel}.{r}x{c}.block_c")})


def serving_space(window_ms=(1.0, 2.0, 5.0, 10.0, 20.0),
                  max_queue=(32, 64, 128, 256)) -> Space:
    """Serving coalescing window + admission bound (the ``Server``
    consumers of the tuned table)."""
    def validate(cfg):
        if cfg["window_ms"] < 0:
            return "window_ms_negative"
        if cfg["max_queue"] <= 0:
            return "max_queue_nonpositive"
        return None

    return Space(
        name="serving",
        params={"window_ms": Choice("window_ms", tuple(window_ms)),
                "max_queue": Choice("max_queue", tuple(max_queue))},
        default={"window_ms": 5.0, "max_queue": 128},
        validate=validate,
        table_map={"window_ms": ("serving", "window_ms"),
                   "max_queue": ("serving", "max_queue")})


def router_space(hedge_ms=(0.0, 5.0, 10.0, 25.0, 50.0)) -> Space:
    """Router tail-latency hedge delay (0 = hedging off)."""
    return Space(
        name="router",
        params={"hedge_ms": Choice("hedge_ms", tuple(hedge_ms))},
        default={"hedge_ms": 0.0},
        validate=lambda cfg: ("hedge_ms_negative"
                              if cfg["hedge_ms"] < 0 else None),
        table_map={"hedge_ms": ("router", "hedge_ms")})


def decode_space(slots=(2, 4, 8, 16)) -> Space:
    """Continuous-batching decode slot pool size."""
    return Space(
        name="decode",
        params={"slots": Choice("slots", tuple(slots))},
        default={"slots": 8},
        validate=lambda cfg: ("slots_nonpositive"
                              if cfg["slots"] <= 0 else None),
        table_map={"slots": ("decode", "slots")})


def bucket_space(max_batch: int = 8, compile_cap: int = 32) -> Space:
    """Batch-bucket lattice candidates, validity-gated by the REAL
    compile bound: a lattice whose ``BucketGrid.grid_bound()`` exceeds
    ``compile_cap`` is invalid (the PR-4 bounded-compile guarantee is a
    constraint the tuner must never trade away)."""
    cands = []
    pow2 = tuple(b for b in (1, 2, 4, 8, 16, 32, 64) if b <= max_batch)
    for lattice in (pow2, pow2[::2] or pow2, (max_batch,),
                    tuple(range(1, max_batch + 1))):
        lat = tuple(sorted(set(lattice)))
        if lat and lat not in cands:
            cands.append(lat)

    def validate(cfg):
        from ..serving.buckets import BucketGrid
        lat = cfg["batch_buckets"]
        if max(lat) > max_batch:
            return f"bucket_exceeds_max_batch:{max(lat)}>{max_batch}"
        bound = BucketGrid(max_batch, lat).grid_bound()
        if bound > compile_cap:
            return f"grid_bound:{bound}>{compile_cap}"
        return None

    return Space(
        name="buckets",
        params={"batch_buckets": Choice("batch_buckets", tuple(cands))},
        default={"batch_buckets": pow2},
        validate=validate,
        table_map={"batch_buckets": ("buckets", "batch")})
