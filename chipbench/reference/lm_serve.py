"""Decode-step streams of ``serve/<arch>/decode@c<c>@b<b>@p<p>``, written
from a configuration document's published widths (the Hugging Face
``config.json`` keys) and its ``assumed_sizes``; imports nothing of the
program.

One step of ``b`` sequences of ``c`` tokens, a share ``p`` percent of
them a prefix the batch shares.  S is the sum of the step's stream bytes.

    stream            bytes                                    reuse
    w.<kind>          parameters of the kind x weight bytes    S
    w.routed          MoE layers x E (1 - (1 - k/E)^b) x 3 d w_e x 2   S
    kda.state.r / .w  b x L_kda x H x hd^2 x state bytes       S
    kda.conv.r / .w   b x L_kda x (conv - 1) x 3 H hd x 2      S
    mla.lat.r         L_mla x lat x (b c (1 - p) + c p)        S
    mla.lat.r.shared  L_mla x lat x (b - 1) c p                c x lat
    mla.lat.w         b x L_mla x lat                          S
    activations.r     12 b d x 2                               4 b d // 64
    activations.w     6 b d x 2 (write)                        4 b d // 64
    logits            b x vocab x 4 (write)                    infinite

with lat = (kv_lora_rank + qk_rope_head_dim) x cache bytes.  Streams of
zero bytes are left out.
"""

from __future__ import annotations

import math
import re

from . import traffic

NAME = re.compile(r"serve/([^/]+)/decode@c(\d+)@b(\d+)@p(\d+)")


def parameters(cfg: dict) -> dict[str, int]:
    """Parameters of each weight kind over the stack, and of one routed
    expert."""
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    la = cfg["linear_attn_config"]
    h, hd = la["num_heads"], la["head_dim"]
    rank = cfg["assumed_sizes"]["kda_gate_rank"]
    kda = (d * h * hd * 3 + h * hd * d
           + la["short_conv_kernel_size"] * 3 * h * hd
           + 2 * (d * rank + rank * h * hd) + d * h)
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    kv = cfg["kv_lora_rank"]
    q_rank = cfg["q_lora_rank"]
    q = (d * heads * (nope + rope) if not q_rank
         else d * q_rank + q_rank * heads * (nope + rope))
    mla = (q + d * (kv + rope) + kv * heads * (nope + cfg["v_head_dim"])
           + heads * cfg["v_head_dim"] * d)
    dense_layers = cfg["first_k_dense_replace"]
    moe_layers = cfg["num_hidden_layers"] - dense_layers
    expert = 3 * d * cfg["moe_intermediate_size"]
    return {"kda": len(la["kda_layers"]) * kda,
            "mla": len(la["full_attn_layers"]) * mla,
            "dense": dense_layers * 3 * d * cfg["intermediate_size"],
            "shared": moe_layers * cfg["num_shared_experts"] * expert,
            "router": moe_layers * d * cfg["num_experts"],
            "head": cfg["vocab_size"] * d,
            "expert": expert}


def decode_step(cfg: dict, c: int, b: int, p: int,
                name: str = "") -> traffic.TrafficStats:
    sizes = cfg["assumed_sizes"]
    w_bytes, cache = sizes["weight_bytes"], sizes["cache_bytes"]
    la = cfg["linear_attn_config"]
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    l_kda, l_mla = len(la["kda_layers"]), len(la["full_attn_layers"])
    moe_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    e, k = cfg["num_experts"], cfg["num_experts_per_token"]
    lat = (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * cache
    shared = c * p // 100
    n = parameters(cfg)
    touched = e * (1 - (1 - k / e) ** b)
    state = b * l_kda * la["num_heads"] * la["head_dim"] ** 2 \
        * sizes["kda_state_bytes"]
    conv = b * l_kda * (la["short_conv_kernel_size"] - 1) * 3 \
        * la["num_heads"] * la["head_dim"] * cache
    S = None   # the reuse distance S, filled in once the table is summed
    table = [
        ("w.kda", n["kda"] * w_bytes, False, S),
        ("w.mla", n["mla"] * w_bytes, False, S),
        ("w.dense", n["dense"] * w_bytes, False, S),
        ("w.shared", n["shared"] * w_bytes, False, S),
        ("w.router", n["router"] * w_bytes, False, S),
        ("w.routed", moe_layers * touched * n["expert"] * w_bytes, False,
         S),
        ("w.head", n["head"] * w_bytes, False, S),
        ("kda.state.r", state, False, S),
        ("kda.state.w", state, True, S),
        ("kda.conv.r", conv, False, S),
        ("kda.conv.w", conv, True, S),
        ("mla.lat.r", l_mla * lat * (b * (c - shared) + shared), False,
         S),
        ("mla.lat.r.shared", l_mla * lat * (b - 1) * shared, False, c * lat),
        ("mla.lat.w", b * l_mla * lat, True, S),
        ("activations.r", 12.0 * b * d * 2.0, False, 4 * b * d // 64),
        ("activations.w", 6.0 * b * d * 2.0, True, 4 * b * d // 64),
        ("logits", b * vocab * 4.0, True, math.inf),
    ]
    table = [row for row in table if row[1] > 0]
    step = math.fsum(float(row[1]) for row in table)
    streams = tuple(traffic.AccessStream(label, float(nbytes), is_write,
                                         step if reuse is None
                                         else float(reuse))
                    for label, nbytes, is_write, reuse in table)
    per_token = (n["kda"] + n["mla"] + n["dense"] + n["shared"]
                 + n["router"] + n["head"] + moe_layers * k * n["expert"])
    macs = (b * per_token
            + b * l_mla * cfg["num_attention_heads"] * c
            * (2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + b * l_kda * la["num_heads"] * 4 * la["head_dim"] ** 2)
    return traffic.TrafficStats(name, b, False, streams, float(macs))


def stats(cfg: dict, name: str) -> traffic.TrafficStats:
    """The streams of the scenario ``name`` under configuration ``cfg``."""
    m = NAME.fullmatch(name)
    if m is None:
        raise ValueError(f"not a serve scenario: {name!r}")
    _, c, b, p = m.groups()
    return decode_step(cfg, int(c), int(b), int(p), name)
