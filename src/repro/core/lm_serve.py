"""Decode-step cache traffic of a served hybrid LM, with finite reuse.

One scenario is one decode step of a batch of ``b`` sequences, each with
``c`` tokens of context, of which a share ``p`` (in percent) is a prefix
that the whole batch shares: one copy of its MLA latents, read by every
sequence.  The model is a Kimi-Linear-style stack (``LinearAttnSpec``):
Kimi Delta Attention (KDA) layers with a recurrent fp32 state per
sequence, MLA layers with a latent cache, and MoE feed-forward layers
after the leading dense ones.

Unlike ``scenarios.lm_traffic``, whose weights and KV always miss, every
stream that the next decode step touches again gets the step's total
stream bytes ``S`` as its reuse distance: weights, the KDA state and
convolution window, and the latents.  The shared prefix is also re-read
inside the step, by each sequence after the first; the sequences are
scanned one after another inside an MLA layer, so its reuse distance is
one sequence's latent scan, ``c x lat``.

Streams of one step (bytes; ``lat`` = (kv_lora_rank + qk_rope_dim) x 2 B,
the NoPE layers keep the rope-width dimensions unrotated):

    w.kda, w.mla, w.dense, w.shared, w.router, w.head
                      each kind's parameters x 2 B (bf16)           RD S
    w.routed          MoE layers x experts touched x 3 d d_expert x 2 B
    kda.state.r/.w    b x L_kda x H x hd^2 x 4 B (fp32)             RD S
    kda.conv.r/.w     b x L_kda x (k - 1) x 3 H hd x 2 B            RD S
    mla.lat.r         L_mla x lat x (b c (1 - p) + c p)             RD S
    mla.lat.r.shared  L_mla x lat x (b - 1) c p                   RD c lat
    mla.lat.w         b x L_mla x lat (write)                       RD S
    activations.r/.w, logits   as ``lm_traffic`` with tokens = b

Routing is taken as uniform over the experts (the even load the router's
balancing aims at), so the expected number of distinct experts touched by
``b`` tokens of ``top_k`` choices each is ``E (1 - (1 - top_k / E)^b)``:
8 of 256 at b = 1, 163 at b = 32.  MACs per step are ``b`` x the
parameters one token touches (``top_k`` routed experts per MoE layer) +
``b L_mla H c (lat_values + kv_lora_rank)`` (absorbed MLA over the
latents) + ``b L_kda H 4 hd^2`` (the delta-rule state update and read).

Left out: the embedding rows a step gathers (b x d x 2 B), the RMSNorm
scales, KDA's decay constants (A_log, dt_bias) and the router's score
bias; together under 1e-4 of the step's bytes at the published widths.

The modelled kernel order inside a step is layer by layer, and inside a
layer: the attention weights, then each sequence in turn (KDA:
convolution window read, state read, state write, window write; MLA:
shared-prefix latents, private latents, latent write), then the
feed-forward weights (dense, or router, shared and touched experts); the
output head last.  Only the shared prefix's reuse distance depends on it.
"""

from __future__ import annotations

from repro.configs.base import ArchConfig
from repro.core.traffic import INF, AccessStream, TrafficStats

WEIGHT_BYTES = 2.0   # bf16 weights
CACHE_BYTES = 2.0    # bf16 latents and convolution windows
STATE_BYTES = 4.0    # fp32 KDA state


def param_counts(cfg: ArchConfig) -> dict[str, float]:
    """Parameters of each weight kind over the whole stack, and of one
    routed expert (``expert``)."""
    la, mla, moe = cfg.linear_attn, cfg.mla, cfg.moe
    d = cfg.d_model
    h, hd, r = la.n_heads, la.head_dim, la.gate_rank
    kda_layer = (3 * d * h * hd              # q, k, v
                 + h * hd * d                 # output projection
                 + 3 * h * hd * la.conv_kernel  # depthwise short convs
                 + 2 * (d * r + r * h * hd)   # low-rank decay and out gate
                 + d * h)                     # beta
    qk = mla.qk_nope_dim + mla.qk_rope_dim
    q = (d * mla.q_lora_rank + mla.q_lora_rank * cfg.n_heads * qk
         if mla.q_lora_rank else d * cfg.n_heads * qk)
    mla_layer = (q + d * (mla.kv_lora_rank + mla.qk_rope_dim)
                 + mla.kv_lora_rank * cfg.n_heads
                 * (mla.qk_nope_dim + mla.v_head_dim)
                 + cfg.n_heads * mla.v_head_dim * d)
    n_moe = cfg.n_layers - moe.first_dense_layers
    expert = 3 * d * moe.d_expert
    return {"kda": float(len(la.kda_layers) * kda_layer),
            "mla": float(len(la.full_attn_layers) * mla_layer),
            "dense": float(moe.first_dense_layers * 3 * d * moe.dense_d_ff),
            "shared": float(n_moe * moe.n_shared * expert),
            "router": float(n_moe * d * moe.n_experts),
            "head": float(cfg.vocab * d),
            "expert": float(expert)}


def experts_touched(n_experts: int, top_k: int, batch: int) -> float:
    """Expected distinct experts that ``batch`` tokens of ``top_k``
    uniform choices each touch in one MoE layer."""
    return n_experts * (1.0 - (1.0 - top_k / n_experts) ** batch)


def decode_step(cfg: ArchConfig, context: int, batch: int, prefix_pct: int,
                key: str) -> TrafficStats:
    """The streams of one decode step (module docstring), as the scenario
    named ``key``."""
    if context < 1 or batch < 1:
        raise ValueError(f"context and batch must be positive, got "
                         f"c={context} b={batch}")
    if not 0 <= prefix_pct < 100 or context * prefix_pct % 100:
        raise ValueError(f"prefix share must be a percentage in [0, 100) "
                         f"of whole tokens, got p={prefix_pct} at "
                         f"c={context}")
    la, mla, moe = cfg.linear_attn, cfg.mla, cfg.moe
    if la is None or mla is None or moe is None:
        raise ValueError(f"{cfg.name} is not a KDA + MLA + MoE stack")
    c, b, d = context, batch, cfg.d_model
    prefix = c * prefix_pct // 100
    l_kda, l_mla = len(la.kda_layers), len(la.full_attn_layers)
    n_moe = cfg.n_layers - moe.first_dense_layers
    lat_values = mla.kv_lora_rank + mla.qk_rope_dim
    lat = lat_values * CACHE_BYTES
    params = param_counts(cfg)
    touched = experts_touched(moe.n_experts, moe.top_k, b)
    state = b * l_kda * la.n_heads * la.head_dim ** 2 * STATE_BYTES
    conv = b * l_kda * (la.conv_kernel - 1) * 3 * la.n_heads * la.head_dim \
        * CACHE_BYTES
    # (label, bytes, is_write, reuse distance; None = the step's bytes S)
    rows = [(f"w.{kind}", params[kind] * WEIGHT_BYTES, False, None)
            for kind in ("kda", "mla", "dense", "shared", "router")]
    rows += [
        ("w.routed", n_moe * touched * params["expert"] * WEIGHT_BYTES,
         False, None),
        ("w.head", params["head"] * WEIGHT_BYTES, False, None),
        ("kda.state.r", state, False, None),
        ("kda.state.w", state, True, None),
        ("kda.conv.r", conv, False, None),
        ("kda.conv.w", conv, True, None),
        ("mla.lat.r", l_mla * lat * (b * (c - prefix) + prefix), False,
         None),
        ("mla.lat.r.shared", l_mla * lat * (b - 1) * prefix, False, c * lat),
        ("mla.lat.w", b * l_mla * lat, True, None),
        # activations and logits exactly as lm_traffic accounts a decode
        # step of b tokens
        ("activations.r", 12.0 * b * d * 2.0, False, 4 * b * d // 64),
        ("activations.w", 6.0 * b * d * 2.0, True, 4 * b * d // 64),
        ("logits", b * cfg.vocab * 4.0, True, INF),
    ]
    rows = [row for row in rows if row[1] > 0]
    step = sum(row[1] for row in rows)
    streams = tuple(AccessStream(label, nbytes, is_write,
                                 step if reuse is None else reuse)
                    for label, nbytes, is_write, reuse in rows)
    per_token = (sum(params[k] for k in ("kda", "mla", "dense", "shared",
                                         "router", "head"))
                 + n_moe * moe.top_k * params["expert"])
    macs = (b * per_token
            + b * l_mla * cfg.n_heads * c * (lat_values + mla.kv_lora_rank)
            + b * l_kda * la.n_heads * 4 * la.head_dim ** 2)
    return TrafficStats(key, b, False, streams, macs_per_batch=float(macs))
