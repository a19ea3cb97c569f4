"""Per-shape/dtype block-size tuning table for the matmul backends.

Replaces the hardcoded ``block_m/n/k = 256`` defaults that every kernel
wrapper used to carry.  Lookup order:

1. explicit caller override (``api.matmul(..., block_m=...)``) — never touched
2. registered tuning entries, most recently registered first, matched on
   (backend, dtype, shape bounds)
3. the built-in heuristic

Whatever the table yields is then *clamped to the problem*: a block is never
larger than the padded dimension it tiles (no point padding a (8, 64) matmul
to 256x256), never smaller than the hardware minimum (8 sublanes for M, one
permutation tile for K/N — the de-shear operates per 64-wide tile).

The autotuner (``repro.api.autotune``) writes *measured* entries through
:func:`register_measured`: exact-shape rules (min == max == the measured
problem) that outrank the heuristic built-ins, mirrored to a JSON cache on
disk (:func:`cache_path`) that reloads lazily on the first lookup so tuned
entries survive restarts.  See ``docs/tuning.md``.
"""

from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import warnings
from typing import List, NamedTuple, Optional, Union

import jax.numpy as jnp

from repro.api.weights import PERM_TILE

__all__ = [
    "BlockConfig",
    "TuningEntry",
    "register_tuning",
    "register_measured",
    "lookup_blocks",
    "clamp_blocks",
    "cache_path",
    "load_cache",
    "save_cache_record",
]


class BlockConfig(NamedTuple):
    block_m: int
    block_n: int
    block_k: int


@dataclasses.dataclass(frozen=True)
class TuningEntry:
    """One tuning rule: applies when every non-None constraint matches.

    ``max_*`` bound the rule from above (applies while m <= max_m, ...);
    ``min_*`` from below.  Measured entries pin both to the benchmarked
    problem so they never leak onto shapes that were not timed.

    ``epilogue`` is part of the key: a fused epilogue shifts the VMEM
    working set (a dual-weight ``swiglu`` doubles the streamed weight bytes
    and adds a second accumulator; ``residual`` streams an extra (bm, bn)
    block), so a block geometry measured unfused must not leak onto fused
    dispatches.  ``None`` matches any epilogue (heuristic built-ins);
    measured entries pin the exact epilogue they were timed with.
    """

    blocks: BlockConfig
    backend: Optional[str] = None       # None = any backend
    dtype: Optional[str] = None         # operand dtype name, None = any
    max_m: Optional[int] = None
    max_k: Optional[int] = None
    max_n: Optional[int] = None
    min_m: Optional[int] = None
    min_k: Optional[int] = None
    min_n: Optional[int] = None
    epilogue: Optional[str] = None      # None = any epilogue
    source: str = "user"                # user | measured | cache | builtin

    def matches(self, backend: str, dtype: str, m: int, k: int, n: int,
                epilogue: str = "none") -> bool:
        return (
            (self.backend is None or self.backend == backend)
            and (self.dtype is None or self.dtype == dtype)
            and (self.epilogue is None or self.epilogue == epilogue)
            and (self.max_m is None or m <= self.max_m)
            and (self.max_k is None or k <= self.max_k)
            and (self.max_n is None or n <= self.max_n)
            and (self.min_m is None or m >= self.min_m)
            and (self.min_k is None or k >= self.min_k)
            and (self.min_n is None or n >= self.min_n)
        )


_TABLE: List[TuningEntry] = []


def register_tuning(
    blocks,
    *,
    backend: Optional[str] = None,
    dtype: Optional[str] = None,
    max_m: Optional[int] = None,
    max_k: Optional[int] = None,
    max_n: Optional[int] = None,
    min_m: Optional[int] = None,
    min_k: Optional[int] = None,
    min_n: Optional[int] = None,
    epilogue: Optional[str] = None,
    source: str = "user",
) -> TuningEntry:
    """Add a tuning rule (most recently registered wins on overlap).

    The default ``source="user"`` keeps explicitly registered rules ahead
    of lazily loaded cache entries (see :func:`load_cache` precedence).
    """
    entry = TuningEntry(
        blocks=BlockConfig(*blocks), backend=backend, dtype=dtype,
        max_m=max_m, max_k=max_k, max_n=max_n,
        min_m=min_m, min_k=min_k, min_n=min_n, epilogue=epilogue,
        source=source,
    )
    _TABLE.insert(0, entry)
    return entry


def _pow2_ceil(v: int) -> int:
    return 1 << max(0, int(v) - 1).bit_length()


def clamp_blocks(
    blocks: BlockConfig, m: int, k: int, n: int, perm_tile: int = PERM_TILE
) -> BlockConfig:
    """Shrink blocks to the problem: never over-block a tiny dimension.

    K/N blocks stay multiples of the permutation tile (the in-kernel
    de-shear is per-tile) — a table entry that isn't is rounded up rather
    than poisoning every dispatch with a kernel-side ValueError; M keeps
    the 8-sublane floor.
    """
    tile_up = lambda v: v + (-v) % perm_tile
    bm = max(8, min(blocks.block_m, _pow2_ceil(m)))
    bn = tile_up(max(perm_tile, min(blocks.block_n, _pow2_ceil(n))))
    bk = tile_up(max(perm_tile, min(blocks.block_k, _pow2_ceil(k))))
    return BlockConfig(bm, bn, bk)


def lookup_blocks(
    backend: str, m: int, k: int, n: int, dtype, *, perm_tile: int = PERM_TILE,
    epilogue: str = "none",
) -> BlockConfig:
    """Resolve block sizes for one dispatch (before caller overrides)."""
    _ensure_cache_loaded()
    dtype_name = jnp.dtype(dtype).name
    for entry in _TABLE:
        if entry.matches(backend, dtype_name, m, k, n, epilogue):
            return clamp_blocks(entry.blocks, m, k, n, perm_tile)
    # heuristic fallback: MXU-aligned 256 cube, shrunk to the problem
    return clamp_blocks(BlockConfig(256, 256, 256), m, k, n, perm_tile)


# ---------------------------------------------------------------------------
# Measured-entry persistence.  The autotuner (repro.api.autotune) registers
# winners through register_measured(), which mirrors them to a JSON cache so
# a fresh process starts from the measured table instead of the heuristics.
CACHE_VERSION = 1
_CACHE_DIR_ENV = "REPRO_DIP_CACHE_DIR"        # override the cache directory
_CACHE_DISABLE_ENV = "REPRO_DIP_NO_TUNING_CACHE"  # set to skip import-time load


def _device_tag() -> str:
    """Filename-safe identifier for the device the entries were measured on
    (block-size winners do not transfer across device generations)."""
    import jax  # deferred: keep module import free of backend initialization

    kind = jax.devices()[0].device_kind
    tag = "".join(c if c.isalnum() else "-" for c in kind.lower()).strip("-")
    return tag or "unknown"


def cache_dir() -> pathlib.Path:
    env = os.environ.get(_CACHE_DIR_ENV)
    if env:
        return pathlib.Path(env)
    xdg = os.environ.get("XDG_CACHE_HOME")
    base = pathlib.Path(xdg) if xdg else pathlib.Path.home() / ".cache"
    return base / "repro-dip"


def cache_path(path: Union[str, pathlib.Path, None] = None) -> pathlib.Path:
    """The tuning-cache file for this device (``tuning-<device>.json``)."""
    if path is not None:
        return pathlib.Path(path)
    return cache_dir() / f"tuning-{_device_tag()}.json"


def _record_key(rec: dict) -> tuple:
    # older caches predate the epilogue axis; their records were measured on
    # the unfused path, so they key (and match) as epilogue="none"
    return (rec["backend"], rec["dtype"], rec.get("epilogue", "none"),
            rec["m"], rec["k"], rec["n"])


def _read_cache(path: pathlib.Path) -> List[dict]:
    if not path.exists():
        return []
    payload = json.loads(path.read_text())
    if payload.get("version") != CACHE_VERSION:
        raise ValueError(
            f"tuning cache {path} has version {payload.get('version')!r}, "
            f"expected {CACHE_VERSION}"
        )
    return list(payload.get("entries", []))


def save_cache_record(
    rec: dict, path: Union[str, pathlib.Path, None] = None
) -> pathlib.Path:
    """Insert-or-replace one measured record (keyed on backend/dtype/shape)."""
    p = cache_path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    try:
        existing = _read_cache(p)
    except Exception as exc:
        # self-heal: a corrupt/foreign-version cache must not make every
        # future autotune run crash at persist time — start a fresh file
        warnings.warn(f"replacing unreadable tuning cache {p}: {exc}")
        existing = []
    entries = [e for e in existing if _record_key(e) != _record_key(rec)]
    entries.append(rec)
    entries.sort(key=_record_key)
    tmp = p.with_suffix(".json.tmp")
    tmp.write_text(json.dumps(
        {"version": CACHE_VERSION, "device": _device_tag(), "entries": entries},
        indent=2, sort_keys=True,
    ) + "\n")
    tmp.replace(p)  # atomic: a concurrent reader never sees a torn file
    return p


def register_measured(
    blocks,
    *,
    backend: str,
    dtype: str,
    m: int,
    k: int,
    n: int,
    epilogue: str = "none",
    time_us: Optional[float] = None,
    persist: bool = True,
    path: Union[str, pathlib.Path, None] = None,
) -> TuningEntry:
    """Register an autotuned winner: an exact-shape (and exact-epilogue)
    rule, optionally mirrored to the on-disk cache so it survives restarts."""
    entry = register_tuning(
        blocks, backend=backend, dtype=dtype,
        max_m=m, max_k=k, max_n=n, min_m=m, min_k=k, min_n=n,
        epilogue=epilogue, source="measured",
    )
    if persist:
        bc = entry.blocks
        rec = {
            "backend": backend, "dtype": dtype, "m": m, "k": k, "n": n,
            "epilogue": epilogue,
            "block_m": bc.block_m, "block_n": bc.block_n, "block_k": bc.block_k,
        }
        if time_us is not None:
            rec["time_us"] = round(float(time_us), 3)
        save_cache_record(rec, path)
    return entry


def load_cache(path: Union[str, pathlib.Path, None] = None) -> int:
    """Register every record from the on-disk cache (newest-registered wins);
    returns the number of entries loaded.  Runs lazily on first table access
    (not at import: resolving the cache filename initializes the JAX backend,
    which importers like launch/dryrun must control themselves)."""
    p = cache_path(path)
    entries = [
        TuningEntry(
            blocks=BlockConfig(rec["block_m"], rec["block_n"], rec["block_k"]),
            backend=rec["backend"], dtype=rec["dtype"],
            max_m=rec["m"], max_k=rec["k"], max_n=rec["n"],
            min_m=rec["m"], min_k=rec["k"], min_n=rec["n"],
            epilogue=rec.get("epilogue", "none"),
            source="cache",
        )
        for rec in _read_cache(p)
    ]
    # precedence: explicitly registered rules > cached winners > built-ins
    idx = next(
        (i for i, e in enumerate(_TABLE) if e.source == "builtin"), len(_TABLE)
    )
    _TABLE[idx:idx] = entries
    return len(entries)


_CACHE_LOADED = False


def _ensure_cache_loaded() -> None:
    """Load persisted measured entries once, on the first lookup.

    Deliberately NOT at import: resolving the cache filename queries the
    device kind, which initializes the JAX backend — importers (e.g.
    launch/dryrun's XLA_FLAGS games) must stay in control of that.  Cached
    entries splice in behind explicitly registered rules, so lazy loading
    never demotes a rule the caller added before the first lookup.
    """
    global _CACHE_LOADED
    if _CACHE_LOADED or os.environ.get(_CACHE_DISABLE_ENV):
        return
    _CACHE_LOADED = True  # set first so a load failure is not retried per call
    try:
        load_cache()
    except Exception as exc:  # a corrupt cache must not break dispatch
        warnings.warn(f"ignoring unreadable tuning cache: {exc}")


# ---------------------------------------------------------------------------
# Built-in entries.  Narrower operands afford deeper K blocks at the same
# VMEM budget (acc scratch is f32/i32 at block_m x block_n regardless);
# the wavefront-emulation path tiles K/N at the physical array dimension.
register_tuning((256, 256, 256), dtype="float32", source="builtin")
register_tuning((256, 256, 512), dtype="bfloat16", source="builtin")
register_tuning((256, 256, 512), dtype="int8", source="builtin")
register_tuning((128, PERM_TILE, PERM_TILE), backend="pallas_systolic",
                source="builtin")
# quantized backends (keyed on the ACTIVATION dtype at dispatch): int8
# weight blocks are 4x narrower than f32 at the same geometry, but the
# accumulator stays int32/f32 at full (block_m x block_n) width — deepen K,
# keep the output tile at the f32 default.
register_tuning((256, 256, 512), backend="dip_int8w", source="builtin")
register_tuning((256, 256, 512), backend="dip_fp8", source="builtin")
