"""What decides ``correct``: served tokens against the plain reference.

Once the window has closed, a sample of the requests that finished in it,
drawn from the seed and always holding the one with the most served
tokens, is run through the float32 reference once, teacher-forced on each
prompt and its served tokens.  At every served position the reference's
best logit minus its logit of the token the program served is a gap; a
greedy server that computes what the reference computes serves the
reference's best token or, where two nearly tie, one within rounding of
it.  The numbers compared are those the configuration's ``correct``
section gives a limit: the widest gap (``max_logit_gap``), or the mean
gap over every served token (``mean_logit_gap``) where the widest gap
does not separate sound runs from the control (a mixture of experts,
whose router's near ties move the widest gap in bfloat16 about as far as
in float8), and always at least ``min_tokens_compared`` tokens, none
outside the vocabulary.

The control (``control_gaps``) is the same reference run in a lower
precision in the program's place: at each position, the gap of the token
that the lower precision puts first.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256


@dataclasses.dataclass
class Served:
    prompt: np.ndarray   # (n,) int32
    tokens: np.ndarray   # (m,) int32, what the program served


def reference(cfg: dict) -> Any:
    return importlib.import_module(
        f"benchmarks.chip.references.{cfg['model_type']}")


def sample(finished: list[Served], k: int, seed: int) -> list[Served]:
    """The request with the most served tokens and ``k - 1`` others drawn
    from the seed."""
    if not finished:
        return []
    longest = max(range(len(finished)),
                  key=lambda i: (len(finished[i].tokens), -i))
    rest = [i for i in range(len(finished)) if i != longest]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [finished[longest]] + [finished[rest[int(j)]] for j in pick]


def batch(served: list[Served], n_rows: int, max_rows: int, max_out: int
          ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Fixed-shape inputs, so the reference compiles once per cell:
    tokens (n_rows, S), score positions (n_rows, max_out), their targets
    and a mask of real positions.  Missing rows repeat the last one
    under a zero mask."""
    s = max(QUERY_BLOCK, -(-max_rows // QUERY_BLOCK) * QUERY_BLOCK)
    toks = np.zeros((n_rows, s), np.int32)
    pos = np.zeros((n_rows, max_out), np.int32)
    tgt = np.zeros((n_rows, max_out), np.int32)
    mask = np.zeros((n_rows, max_out), bool)
    for r in range(n_rows):
        sv = served[min(r, len(served) - 1)]
        n, m = len(sv.prompt), len(sv.tokens)
        seq = np.concatenate([sv.prompt, sv.tokens[:-1]])
        toks[r, :len(seq)] = seq
        pos[r, :m] = n - 1 + np.arange(m)
        pos[r, m:] = n - 1 + m - 1
        tgt[r, :m] = sv.tokens
        mask[r, :m] = r < len(served)
    return toks, pos, tgt, mask


@jax.jit
def _gaps(ref_logits: jax.Array, chosen: jax.Array) -> jax.Array:
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, chosen[..., None], axis=-1)[..., 0]
    return best - got


def served_gaps(cfg: dict, seed: int, served: list[Served], n_rows: int,
                max_rows: int, max_out: int) -> np.ndarray:
    """Gap of every served token (a flat array)."""
    toks, pos, tgt, mask = batch(served, n_rows, max_rows, max_out)
    ref = reference(cfg).logits_at(cfg, seed, toks, pos, "f32")
    gaps = np.asarray(_gaps(ref[..., :cfg["vocab_size"]],
                            jnp.asarray(tgt)))
    return gaps[mask]


def control_gaps(cfg: dict, seed: int, served: list[Served], n_rows: int,
                 max_rows: int, max_out: int, mode: str = "fp8"
                 ) -> np.ndarray:
    """Gap of the token that the lower precision puts first at each served
    position (the control of the comparison)."""
    toks, pos, _, mask = batch(served, n_rows, max_rows, max_out)
    ref_mod = reference(cfg)
    low = ref_mod.logits_at(cfg, seed, toks, pos, mode)
    chosen = jnp.argmax(low[..., :cfg["vocab_size"]], axis=-1)
    del low
    ref = ref_mod.logits_at(cfg, seed, toks, pos, "f32")
    gaps = np.asarray(_gaps(ref[..., :cfg["vocab_size"]], chosen))
    return gaps[mask]


def verdict(cfg: dict, gaps: np.ndarray, served: list[Served]
            ) -> tuple[bool, dict]:
    """``correct`` and each number compared with its limit."""
    lim = cfg["correct"]
    vocab = cfg["vocab_size"]
    out_of_vocab = sum(int(np.sum((s.tokens < 0) | (s.tokens >= vocab)))
                       for s in served)
    readings: dict[str, Optional[float]] = {
        "max_logit_gap": float(gaps.max()) if gaps.size else None,
        "mean_logit_gap": float(gaps.mean()) if gaps.size else None}
    checks = {name: {"value": readings[name], "limit": lim[name],
                     "at_most": True}
              for name in readings if name in lim}
    checks["tokens_compared"] = {"value": int(gaps.size),
                                 "limit": lim["min_tokens_compared"],
                                 "at_most": False}
    checks["tokens_out_of_vocab"] = {"value": out_of_vocab, "limit": 0,
                                     "at_most": True}
    ok = all(c["value"] is not None and (
        c["value"] <= c["limit"] if c["at_most"] else c["value"] >= c["limit"])
        for c in checks.values())
    return ok, checks
