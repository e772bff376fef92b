"""The sparse-expert family: a causal GQA decoder whose layers differ by
kind, window or full attention, each followed by a dropless expert layer
that holds a share of the router's experts (Mellum2-12B-A2.5B).

Per layer: x + attn(rmsnorm(x)), then x + experts(rmsnorm(x)).  Attention
layers of kind ``sliding_attention`` see the ``sliding_window`` keys up to
the query, RoPE plain; ``full_attention`` layers see every earlier key of
the document, RoPE with YaRN scaling (``rope_parameters``).  The router
(``router_experts`` outputs) takes a softmax, keeps the top
``num_experts_per_tok`` and renormalizes them; the layer holds
``num_experts`` experts from ``first_held_expert`` and adds each token's
SwiGLU outputs of the held experts it chose, by their gates.  The experts
held on other chips of the deployment add nothing here, in the program and
in this reference alike.  Loss: cross-entropy over the live tokens plus
``router_aux_loss_coef`` times the layers' mean load-balance loss, each
over the router's whole distribution and all the microbatch's slots.

The program holds one period of layers unscanned (one pattern group), so
every layer's tensors are tensors of their own here too: ``layer<i>.<w>``.
What the harness needs of a family is listed in ``families/dense.py``; this
one adds ``expert_work``, the least work of the held experts' matmuls.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import reference as ref
from benchmarks.chip import work

KINDS = {"sliding_attention": "swa", "full_attention": "attn"}
RMS_EPS = 1e-6  # the program's norm epsilon (repro.models.common.apply_norm)
Q_BLOCK = 512
ATTN = ("wq", "wk", "wv", "wo")
EXPERTS = ("expert_wi", "expert_wg", "expert_wd")


def dims(conf: Dict) -> Dict[str, int]:
    return {
        "L": int(conf["num_hidden_layers"]), "D": int(conf["hidden_size"]),
        "H": int(conf["num_attention_heads"]), "KV": int(conf["num_key_value_heads"]),
        "hd": int(conf["head_dim"]), "F": int(conf["moe_intermediate_size"]),
        "V": int(conf["vocab_size"]), "E": int(conf["router_experts"]),
        "held": int(conf["num_experts"]), "first": int(conf["first_held_expert"]),
        "top_k": int(conf["num_experts_per_tok"]), "W": int(conf["sliding_window"]),
    }


def kinds(conf: Dict) -> List[str]:
    """The program's block kind of each held layer."""
    return [KINDS[t] for t in conf["layer_types"][:int(conf["num_hidden_layers"])]]


def leaf_shapes(conf: Dict) -> Dict[str, Tuple[int, ...]]:
    n = dims(conf)
    D, q, kv, F, held = n["D"], n["H"] * n["hd"], n["KV"] * n["hd"], n["F"], n["held"]
    s = {"embed": (n["V"], D), "final.scale": (D,), "head": (D, n["V"])}
    for i in range(n["L"]):
        s.update({f"layer{i}.ln1_scale": (D,), f"layer{i}.ln2_scale": (D,),
                  f"layer{i}.wq": (D, q), f"layer{i}.wk": (D, kv), f"layer{i}.wv": (D, kv),
                  f"layer{i}.wo": (q, D), f"layer{i}.router": (D, n["E"]),
                  f"layer{i}.expert_wi": (held, D, F), f"layer{i}.expert_wg": (held, D, F),
                  f"layer{i}.expert_wd": (held, F, D)})
    return s


def _rope_parameters(conf: Dict, kind: str) -> Dict:
    return conf["rope_parameters"]["full_attention" if kind == "attn" else "sliding_attention"]


def model_config(conf: Dict):
    """The program's ``ModelConfig``, after checking that the program
    computes what the configuration states."""
    from repro.configs import ModelConfig
    from repro.configs.base import MoEConfig, YarnConfig

    n = dims(conf)
    stated = {"hidden_act": "silu", "attention_bias": False, "norm_topk_prob": True,
              "rms_norm_eps": RMS_EPS, "tie_word_embeddings": False, "use_sliding_window": True}
    for key, value in stated.items():
        if conf[key] != value:
            raise ValueError(f"{key} {conf[key]!r} cannot be run: the program computes {value!r}")
    if any(t != "sparse" for t in conf["mlp_layer_types"][:n["L"]]):
        raise ValueError("every held layer's MLP must be sparse")
    types, period = conf["layer_types"], kinds(conf)
    if len(types) % n["L"] or types != types[:n["L"]] * (len(types) // n["L"]):
        raise ValueError("the held layers must be one period of layer_types")
    full, window = _rope_parameters(conf, "attn"), _rope_parameters(conf, "swa")
    if full["rope_theta"] != window["rope_theta"] or window["rope_type"] != "default":
        raise ValueError("the program takes one rope_theta, and plain RoPE on window layers")
    yarn = None
    if full["rope_type"] == "yarn":
        yarn = YarnConfig(factor=float(full["factor"]),
                          original_max_positions=int(full["original_max_position_embeddings"]),
                          beta_fast=float(full["beta_fast"]), beta_slow=float(full["beta_slow"]),
                          attention_factor=float(full["attention_factor"]))
    moe = MoEConfig(n_experts=n["E"], top_k=n["top_k"], capacity_factor=None,
                    router_aux_weight=float(conf["router_aux_loss_coef"]), router_z_weight=0.0,
                    n_held=n["held"], first_held=n["first"])
    cfg = ModelConfig(
        name=conf.get("name", "chipbench"), family="moe", n_layers=n["L"], d_model=n["D"],
        n_heads=n["H"], n_kv_heads=n["KV"], d_ff=n["F"], vocab_size=n["V"], head_dim=n["hd"],
        block_pattern=tuple(period), sliding_window=n["W"], rope_theta=float(full["rope_theta"]),
        rope_yarn=yarn, norm="rmsnorm", act="swiglu", moe=moe, causal=True,
        tie_embeddings=False)
    if cfg.n_groups() != 1:
        raise ValueError("the layout holds one period of layers, as one unscanned group")
    return cfg


def to_program(bp: Dict, cfg) -> Dict:
    """The benchmark's weights as the program's parameter tree (the same
    arrays, no copies)."""
    group = {}
    for i in range(cfg.model.n_layers):
        w = lambda name: bp[f"layer{i}.{name}"]  # noqa: E731
        group[f"pos{i}"] = {"ln1": {"scale": w("ln1_scale")}, "ln2": {"scale": w("ln2_scale")},
                            "attn": {a: w(a) for a in ATTN},
                            "moe": {m: w(m) for m in ("router",) + EXPERTS}}
    return {"embed": {"embed": bp["embed"]}, "groups": [group], "tail": [],
            "final_norm": {"scale": bp["final.scale"]}, "head": bp["head"]}


def from_program(tree: Dict) -> Dict:
    """Inverse of ``to_program``."""
    bp = {"embed": tree["embed"]["embed"], "final.scale": tree["final_norm"]["scale"],
          "head": tree["head"]}
    for pos, layer in tree["groups"][0].items():
        i = int(pos[len("pos"):])
        bp[f"layer{i}.ln1_scale"] = layer["ln1"]["scale"]
        bp[f"layer{i}.ln2_scale"] = layer["ln2"]["scale"]
        for group in ("attn", "moe"):
            bp.update({f"layer{i}.{w}": x for w, x in layer[group].items()})
    return bp


# ---------------------------------------------------------------------------
# the plain reference (float32, highest precision; nothing of the program)
# ---------------------------------------------------------------------------


def rope_frequencies(conf: Dict, kind: str) -> Tuple[np.ndarray, float]:
    """(inverse frequencies, factor on cos and sin) of a layer kind, from
    the configuration's ``rope_parameters`` (YaRN: HF
    ``_compute_yarn_parameters``)."""
    rp, hd = _rope_parameters(conf, kind), int(conf["head_dim"])
    theta = float(rp["rope_theta"])
    pos_freqs = theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    if rp["rope_type"] == "default":
        return (1.0 / pos_freqs).astype(np.float32), 1.0
    orig, factor = float(rp["original_max_position_embeddings"]), float(rp["factor"])

    def c(rotations):
        return hd * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(c(float(rp["beta_fast"]))), 0)
    high = min(math.ceil(c(float(rp["beta_slow"]))), hd - 1)
    ramp = np.clip((np.arange(hd // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv = (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1.0 - ramp)
    return inv.astype(np.float32), float(rp["attention_factor"])


def rope(x, pos, inv_freq, scale):
    ang = pos[..., None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(ang)[:, :, None, :] * scale, jnp.sin(ang)[:, :, None, :] * scale
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def attention(q, k, v, pos, seg, window: int, quant: bool):
    """Causal in-document attention over blocks of query rows; ``window`` >
    0 keeps the keys whose position is within ``window`` of the query's.
    Such keys lie at most ``window`` - 1 rows back, so a window layer's
    block reads only the rows from ``window`` before it."""
    b, s, h, hd = q.shape
    g = h // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    blk = min(Q_BLOCK, s)
    nb = -(-s // blk)
    pad = nb * blk - s
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(b, nb, blk, h, hd)
    qpos = jnp.pad(pos, ((0, 0), (0, pad)), constant_values=-1).reshape(b, nb, blk)
    qseg = jnp.pad(seg, ((0, 0), (0, pad)), constant_values=-1).reshape(b, nb, blk)
    back = -(-window // blk) * blk if window else 0  # rows before a block it may read
    kp, vp = (jnp.pad(t, ((0, 0), (back, pad), (0, 0), (0, 0))) for t in (k, v))
    kpos = jnp.pad(pos, ((0, 0), (back, pad)), constant_values=-1)
    kseg = jnp.pad(seg, ((0, 0), (back, pad)), constant_values=-1)
    span = back + blk if window else kp.shape[1]

    @jax.checkpoint
    def block(args):
        i, qi, qp, qs = args
        lo = i * blk if window else 0
        ki, vi = (jax.lax.dynamic_slice_in_dim(t, lo, span, axis=1) for t in (kp, vp))
        kpi, ksi = (jax.lax.dynamic_slice_in_dim(t, lo, span, axis=1) for t in (kpos, kseg))
        sc = ref.mm("bqhd,bkhd->bhqk", qi, ki, quant) / math.sqrt(hd)
        ok = ((qp[:, :, None] >= 0) & (kpi[:, None, :] >= 0)
              & (qs[:, :, None] == ksi[:, None, :]) & (kpi[:, None, :] <= qp[:, :, None]))
        if window:
            ok &= kpi[:, None, :] > qp[:, :, None] - window
        ok = ok[:, None]
        sc = jnp.where(ok, sc, -1e30)
        e = jnp.where(ok, jnp.exp(sc - jnp.max(sc, axis=-1, keepdims=True)), 0.0)
        p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
        return ref.mm("bhqk,bkhd->bqhd", p, vi, quant)

    out = jax.lax.map(block, (jnp.arange(nb), jnp.moveaxis(qb, 1, 0),
                              jnp.moveaxis(qpos, 1, 0), jnp.moveaxis(qseg, 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(b, nb * blk, h, hd)[:, :s]


def experts(conf: Dict, p: Dict, h, quant: bool):
    """(the held experts' part of the layer's output, the load-balance
    loss): every held expert on every token, weighted by the gate of the
    choice that took it (0 where none did)."""
    n = dims(conf)
    probs = jax.nn.softmax(ref.mm("bsd,de->bse", h, p["router"], quant), axis=-1)
    w, idx = jax.lax.top_k(probs, n["top_k"])
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    gates = jnp.sum(w[..., None] * jax.nn.one_hot(idx - n["first"], n["held"]), axis=-2)
    chosen = jnp.sum(jax.nn.one_hot(idx, n["E"]), axis=-2)  # (B, S, E)
    frac = jnp.mean(chosen, axis=(0, 1)) / n["top_k"]
    lb = n["E"] * jnp.sum(frac * jnp.mean(probs, axis=(0, 1)))
    out = 0.0
    for e in range(n["held"]):
        up = ref.mm("bsd,df->bsf", h, p["expert_wi"][e], quant)
        f = jax.nn.silu(ref.mm("bsd,df->bsf", h, p["expert_wg"][e], quant)) * up
        out = out + gates[..., e:e + 1] * ref.mm("bsf,fd->bsd", f, p["expert_wd"][e], quant)
    return out, lb


def layer(conf: Dict, kind: str, p: Dict, x, pos, seg, quant: bool):
    """One block: (x after it, its load-balance loss)."""
    n = dims(conf)
    b, s = pos.shape
    h = ref.norm(x, p["ln1_scale"], 0.0, "rmsnorm", RMS_EPS)
    q = ref.mm("bsd,de->bse", h, p["wq"], quant).reshape(b, s, n["H"], n["hd"])
    k = ref.mm("bsd,de->bse", h, p["wk"], quant).reshape(b, s, n["KV"], n["hd"])
    v = ref.mm("bsd,de->bse", h, p["wv"], quant).reshape(b, s, n["KV"], n["hd"])
    inv_freq, scale = rope_frequencies(conf, kind)
    q, k = rope(q, pos, inv_freq, scale), rope(k, pos, inv_freq, scale)
    a = attention(q, k, v, pos, seg, n["W"] if kind == "swa" else 0, quant)
    x = x + ref.mm("bse,ed->bsd", a.reshape(b, s, -1), p["wo"], quant)
    out, lb = experts(conf, p, ref.norm(x, p["ln2_scale"], 0.0, "rmsnorm", RMS_EPS), quant)
    return x + out, lb


def loss(conf: Dict, params: Dict, mb: Dict, quant: bool = False):
    """Mean cross-entropy over the live tokens of one microbatch, plus the
    weighted mean of the layers' load-balance losses."""
    pos, seg = mb["positions"], mb["segments"]
    x = params["embed"][mb["tokens"]]
    lbs = []
    for i, kind in enumerate(kinds(conf)):
        p = {k[len(f"layer{i}."):]: v for k, v in params.items() if k.startswith(f"layer{i}.")}
        step = jax.checkpoint(functools.partial(layer, conf, kind, quant=quant))
        x, lb = step(p, x, pos, seg)
        lbs.append(lb)
    x = ref.norm(x, params["final.scale"], 0.0, "rmsnorm", RMS_EPS)
    ce = ref.mean_nll(x, params["head"], mb["targets"], mb["mask"], quant)
    return ce + float(conf["router_aux_loss_coef"]) * jnp.mean(jnp.stack(lbs))


# ---------------------------------------------------------------------------
# the least work of a step
# ---------------------------------------------------------------------------


def window_pairs(piece_lens: np.ndarray, window: int) -> int:
    """Live causal (query, key) pairs within ``window`` positions: a query
    i of a piece sees min(i + 1, window) keys."""
    n = piece_lens.astype(np.int64)
    short = np.minimum(n, window)
    return int(np.sum(short * (short + 1) // 2 + (n - short) * window))


def pairs(conf: Dict, pieces: np.ndarray) -> Dict[str, int]:
    """Live pairs of a step's document pieces in a layer of each kind."""
    return {"swa": window_pairs(pieces, int(conf["sliding_window"])),
            "attn": work.live_pairs(pieces, True)}


def _layer_pairs(conf: Dict, pairs: Dict[str, int]) -> int:
    """Live pairs summed over the held layers."""
    return sum(pairs[k] for k in kinds(conf))


def matmul_params(conf: Dict) -> int:
    """Weights a live token multiplies by: per layer q, k, v, o, the router
    and, at the nominal share, top_k * held / router_experts experts' three
    matrices; the vocabulary projection once."""
    n = dims(conf)
    attn = 2 * n["D"] * n["H"] * n["hd"] + 2 * n["D"] * n["KV"] * n["hd"]
    routed = n["top_k"] * n["held"] / n["E"]
    return int(n["L"] * (attn + n["D"] * n["E"] + routed * 3 * n["D"] * n["F"])
               + n["D"] * n["V"])


def model_flops(conf: Dict, live_tokens: int, pairs) -> float:
    """Forward and backward, no recompute: 6 per matmul weight per live
    token and 12 * H * hd per live pair of each layer."""
    n = dims(conf)
    return (6.0 * matmul_params(conf) * live_tokens
            + 12.0 * n["H"] * n["hd"] * _layer_pairs(conf, pairs))


def attention_fwd(conf: Dict, live_tokens: int, pairs, itemsize: int):
    """(FLOPs, bytes) of the attention forward over all layers: 4 * hd per
    head and live pair of each layer's kind; q, k, v read and o written
    once per live token."""
    n = dims(conf)
    flops = 4.0 * n["hd"] * n["H"] * _layer_pairs(conf, pairs)
    nbytes = float(live_tokens) * n["L"] * (2 * n["H"] + 2 * n["KV"]) * n["hd"] * itemsize
    return flops, nbytes


def attention_bwd(conf: Dict, live_tokens: int, pairs, itemsize: int):
    """(FLOPs, bytes) of the attention backward over all layers: 8 * hd per
    head and live pair; q, k, v, o, do read and dq, dk, dv written once."""
    n = dims(conf)
    flops = 8.0 * n["hd"] * n["H"] * _layer_pairs(conf, pairs)
    nbytes = float(live_tokens) * n["L"] * (4 * n["H"] + 4 * n["KV"]) * n["hd"] * itemsize
    return flops, nbytes


def expert_work(conf: Dict, rows: int, microbatches: int, itemsize: int):
    """(FLOPs, bytes) of the held experts' matmuls, forward and backward, no
    recompute, for ``rows`` routed (token, choice) rows over all layers and
    ``microbatches`` microbatches: 6 * 3 * D * F per row; each row's input
    and output read or written once a pass (forward x in, y out; backward x
    and dy in, dx out); per layer and microbatch the held experts' three
    matrices read forward and backward and their gradients written."""
    n = dims(conf)
    flops = 18.0 * n["D"] * n["F"] * rows
    weights = 3.0 * n["held"] * n["D"] * n["F"]
    nbytes = (5.0 * n["D"] * rows + 3.0 * weights * n["L"] * microbatches) * itemsize
    return flops, nbytes
