"""Useful model FLOPs, from a configuration's published keys alone.

The count is the work the served tokens need, whatever computes them: 2
FLOPs per multiply-add of every matmul weight a token uses (for a mixture
of experts only the router, the top-k routed experts and the shared
expert), attention at the token's real context length, and the LM head
once per output token.  Padding, recomputed prefixes and capacity slack
are not counted, so no implementation can push a share of the peak past
100% by doing more work.
"""

from __future__ import annotations

from typing import Any, Optional


def _dims(cfg: dict) -> tuple[int, int, int, int]:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    k = cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // h
    return d, h, k, dh


def matmul_params_per_layer(cfg: dict) -> int:
    """Weights one token multiplies through in one layer."""
    d, h, k, dh = _dims(cfg)
    attn = d * h * dh + 2 * d * k * dh + h * dh * d
    if cfg.get("num_experts"):
        fe = cfg["moe_intermediate_size"]
        ffn = (d * cfg["num_experts"]                       # router
               + cfg["num_experts_per_tok"] * 3 * d * fe)   # routed experts
        fs = cfg.get("shared_expert_intermediate_size") or 0
        if fs:
            ffn += 3 * d * fs + d                           # shared + gate
    else:
        ffn = 3 * d * cfg["intermediate_size"]
    return attn + ffn


def attention_flops_per_layer(cfg: dict, context: int) -> int:
    """Scores and weighted values of one query over ``context`` keys."""
    _, h, _, dh = _dims(cfg)
    return 4 * h * dh * context


def head_flops(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def token_flops(cfg: dict, context: int) -> int:
    """One token through every layer at ``context`` keys (no head)."""
    n = cfg["num_hidden_layers"]
    return n * (2 * matmul_params_per_layer(cfg)
                + attention_flops_per_layer(cfg, context))


def prompt_flops(cfg: dict, n_prompt: int) -> int:
    """A whole prompt from position 0, each token at context p + 1, and the
    head once (the first output token)."""
    n = cfg["num_hidden_layers"]
    mm = n * 2 * matmul_params_per_layer(cfg) * n_prompt
    ctx = n_prompt * (n_prompt + 1) // 2
    return mm + n * attention_flops_per_layer(cfg, ctx) + head_flops(cfg)


def decode_flops(cfg: dict, context: int) -> int:
    """One decode step: the token at position ``context - 1`` and its head."""
    return token_flops(cfg, context) + head_flops(cfg)


def served_flops(cfg: dict, requests: list[tuple[int, int]],
                 prefix_hit_tokens: int) -> int:
    """Useful FLOPs of ``(prompt length, tokens delivered)`` requests whose
    prompts were computed in the window: each prompt, then every delivered
    token after the first (which the prompt's pass yields)."""
    return part_flops(cfg, [(p, 0, n) for p, n in requests],
                      prefix_hit_tokens)


def part_flops(cfg: dict, requests: list[tuple[int, int, int]],
               prefix_hit_tokens: int) -> int:
    """Useful FLOPs of the delivered tokens ``first`` to ``last - 1`` of
    ``(prompt length, first, last)`` requests, counted from 0: token 0 is
    the prompt's pass (the whole prompt and the head once), token j > 0 a
    decode step at context prompt + j.  Prompt tokens served from the
    prefix cache are taken out at the longest context of any prompt
    counted, so the count is a lower bound."""
    total = 0
    longest = 0
    for n_prompt, first, last in requests:
        if first == 0 and last > 0:
            longest = max(longest, n_prompt)
            total += prompt_flops(cfg, n_prompt)
        total += sum(decode_flops(cfg, n_prompt + j)
                     for j in range(max(first, 1), last))
    return max(total - prefix_hit_tokens * token_flops(cfg, longest), 0)


def busy_mfu(run: Any) -> Optional[float]:
    """Useful FLOPs of the traced part of a run's window (the tokens
    delivered in it, and the prefix hits counted in it) over the device
    busy seconds of every chip it ran on (the trace's mean busy seconds
    times its ``devices``) times one chip's peak bf16 FLOP/s, in
    percent."""
    if run.trace is None or run.trace["busy_s"] <= 0 or run.peak is None:
        return None
    lo = run.traced_from
    reqs = []
    for s in run.sent:
        ts = run.times(s)
        first = 0 if lo is None else sum(t < lo for t in ts)
        reqs.append((s.prompt_len, first, len(ts)))
    tel = run.telemetry if lo is None else run.traced_telemetry
    useful = part_flops(run.cfg, reqs, tel["shared_hits"] * run.block_size)
    if not useful:
        return None
    chip_s = run.trace["busy_s"] * run.trace["devices"]
    return 100.0 * useful / (chip_s * run.peak.bf16_flops)
