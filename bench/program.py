"""The one place the benchmark touches the program under test.

It turns a configuration file into the program's ``ArchConfig``, loads the
benchmark's seeded weights into the program's own storage (``init``-free:
``api.DipWeight.from_natural`` is the program's checkpoint-import step),
reads the engine's counters, and keeps the logit of each token it serves.
"""

from __future__ import annotations

import dataclasses
import functools
import sys
import weakref
from pathlib import Path

import jax
import jax.numpy as jnp

from bench import weights as W

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from repro import api  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import transformer as tf_model  # noqa: E402

# configuration-file key -> ArchConfig field
_FIELDS = {
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim", "num_hidden_layers": "n_layers",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "attention_bias": "qkv_bias",
}


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for a configuration file's dict."""
    base = get_config(conf["program_arch"])
    fields = {f: conf[k] for k, f in _FIELDS.items() if k in conf}
    fields["rope_theta"] = float(fields["rope_theta"])
    return dataclasses.replace(base, **fields, **conf.get("run", {}))


def _pad(a, axis, to):
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, to - a.shape[axis])
    return jnp.pad(a, widths)


@functools.lru_cache(maxsize=None)
def _loader(cfg, frozen_dims):
    dims = W.Dims(frozen_dims)
    template = tf_model.param_template(cfg)

    def store(name, nat, leaf):
        shape, dt = leaf[0], jnp.dtype(leaf[1])
        dip = leaf[3] if len(leaf) > 3 else None
        if dip is not None:
            w = api.DipWeight.from_natural(nat.astype(dt), dip[2])
        else:
            w = nat.astype(dt)
        got = w.data.shape if dip is not None else w.shape
        if tuple(got) != tuple(shape):
            raise ValueError(f"{name}: made {got}, the program stores {shape}")
        return w

    @jax.jit
    def load(key):
        vp = cfg.padded_vocab
        per_layer = [W.layer_weights(key, dims, i) for i in range(dims.layers)]
        layers = {nm: store(nm, jnp.stack([p[nm] for p in per_layer]), leaf)
                  for nm, leaf in template["layers"].items()}
        return {
            "embed": store("embed", _pad(W.embed(key, dims), 0, vp), template["embed"]),
            "final_norm": store("final_norm", W.final_norm(key, dims),
                                template["final_norm"]),
            "lm_head": store("lm_head", _pad(W.head(key, dims), 1, vp),
                             template["lm_head"]),
            "layers": layers,
        }

    return load


def load_params(cfg, dims: W.Dims, key):
    """The program's parameter tree holding the benchmark's weights, made on
    the device in one jitted call."""
    return _loader(cfg, tuple(sorted(dims.items())))(key)


def engine_counters(engine) -> dict:
    """The engine's own work counters."""
    return {"decode_steps": engine._decode_steps,
            "prefill_chunks": engine._prefill_chunks,
            "preemptions": engine._preempt_count}


def record_served_logits(engine) -> dict:
    """{request id: the program's logit of each token it served, in order},
    filled as the engine samples: one float a served token, read from the
    logits rows the engine has already brought to the host."""
    served = {}
    real, owner = type(engine)._sample_rows, weakref.ref(engine)   # no cycle: del frees it

    def sample_rows(logits, reqs):
        toks = real(owner(), logits, reqs)
        for i, r in enumerate(reqs):
            if r is not None:
                served.setdefault(r.rid, []).append(float(logits[i, toks[i]]))
        return toks

    engine._sample_rows = sample_rows
    return served


def next_chunk_offset(engine) -> int:
    """Prompt position at which the engine's next prefill chunk starts: where
    the request being prefilled stands, or 0 for one the step will admit."""
    return engine._prefill_done if engine._prefilling is not None else 0
