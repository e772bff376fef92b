"""Plain reference of a cell's training step: the model's loss and gradients
and the VR-LAMB update, in float32 ``jax.numpy`` at the highest matmul
precision.  It imports nothing of the program.

What it computes, for a configuration file (``configs/*.json``) and a
traffic file (``traffic/*.json``):

  model    the loss of the configuration's family (``families/<family>.py``)
           built from the blocks here; for the dense family: token embedding
           (tied head or a separate one); per layer a pre-norm block
           x + attn(norm(x)), then x + mlp(norm(x)); RoPE on q and k by
           each token's position within its document; attention only between
           live tokens (position >= 0) of the same document, causal where the
           configuration says so, GQA by repeating each kv head over its group
           of query heads; GELU (tanh form) or SiLU-gated MLP; final norm;
           cross-entropy averaged over the live tokens of each microbatch.
  stats    the batch split into k microbatches of consecutive rows; mean and
           mean of squares of their gradients (GradStats).
  update   VR-LAMB (the paper's Alg. 5): clip the mean gradient to global
           norm ``grad_clip``; GSNR r = mean^2 / max(sq_mean - mean^2, 0),
           divided by its mean over the tensor and clipped to [gamma, 1];
           GSNR momentum p (b3) with bias correction; Adam moments of r * g;
           u = Adam direction + weight_decay * w; LAMB trust ratio
           clip(|w|, 0, 10) / |u| per tensor; w += -lr * ratio * u.  A
           tensor here is one named array, with the layer weights of one kind
           stacked over the layers, as the configuration file states.

``quant=True`` is the control: every matmul takes its operands rounded to
float8 e4m3 (4 significant bits, per-tensor scale), forward and backward —
the step below the bfloat16 matmuls the configurations state.
Memory stays bounded at the timed sizes: layers run under a scan with
rematerialization, attention goes over blocks of query rows, and the
vocabulary projection with its loss over blocks of tokens.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Dict, List

import jax
import jax.numpy as jnp

from benchmarks.chip.spec import family
from benchmarks.chip.weights import leaf_norms, make_params, per_layer

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
Q_BLOCK = 512
TOKEN_BLOCK = 1024


def _fp8(x):
    """Round to float8 e4m3 values after scaling the tensor's max to 448."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    y = x / s
    _, e = jnp.frexp(y)
    quantum = jnp.exp2((jnp.maximum(e, -5) - 4).astype(jnp.float32))
    y = jnp.clip(jnp.round(y / quantum) * quantum, -E4M3_MAX, E4M3_MAX)
    return y * s


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST, preferred_element_type=jnp.float32)


def _mm_fp8(spec):
    @jax.custom_vjp
    def f(a, b):
        return _einsum(spec, _fp8(a), _fp8(b))

    def fwd(a, b):
        return f(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        _, vjp = jax.vjp(lambda x, y: _einsum(spec, x, y), _fp8(a), _fp8(b))
        return vjp(_fp8(g))

    f.defvjp(fwd, bwd)
    return f


def mm(spec, a, b, quant):
    """``einsum(spec, a, b)`` in float32 at the highest precision; with
    ``quant`` its operands, and its cotangent backward, rounded to float8."""
    return _mm_fp8(spec)(a, b) if quant else _einsum(spec, a, b)


def norm(x, scale, bias, kind, eps):
    if kind == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def attention(q, k, v, pos, seg, causal, quant):
    """q (B,S,H,hd), k/v (B,S,KV,hd) -> (B,S,H,hd), over blocks of q rows."""
    b, s, h, hd = q.shape
    g = h // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    blk = min(Q_BLOCK, s)
    nb = -(-s // blk)
    pad = nb * blk - s
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(b, nb, blk, h, hd)
    qpos = jnp.pad(pos, ((0, 0), (0, pad)), constant_values=-1).reshape(b, nb, blk)
    qseg = jnp.pad(seg, ((0, 0), (0, pad)), constant_values=-1).reshape(b, nb, blk)

    @jax.checkpoint
    def block(args):
        qi, qp, qs = args  # (B,blk,H,hd), (B,blk), (B,blk)
        sc = mm("bqhd,bkhd->bhqk", qi, k, quant) / math.sqrt(hd)
        ok = (qp[:, :, None] >= 0) & (pos[:, None, :] >= 0) & (qs[:, :, None] == seg[:, None, :])
        if causal:
            ok &= pos[:, None, :] <= qp[:, :, None]
        ok = ok[:, None]
        sc = jnp.where(ok, sc, -1e30)
        e = jnp.where(ok, jnp.exp(sc - jnp.max(sc, axis=-1, keepdims=True)), 0.0)
        p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
        return mm("bhqk,bkhd->bqhd", p, v, quant)

    out = jax.lax.map(block, (jnp.moveaxis(qb, 1, 0), jnp.moveaxis(qpos, 1, 0),
                              jnp.moveaxis(qseg, 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(b, nb * blk, h, hd)[:, :s]


def mean_nll(x, head, targets, mask, quant: bool):
    """Mean cross-entropy of the final hidden states ``x`` (B,S,D) through
    the vocabulary projection ``head`` (D,V) over the tokens ``mask`` keeps,
    the projection and its loss over blocks of tokens."""
    b, s = targets.shape
    flat = x.reshape(b * s, -1)
    tgt, mask = targets.reshape(-1), mask.reshape(-1).astype(jnp.float32)
    blk = min(TOKEN_BLOCK, b * s)
    nb = -(-(b * s) // blk)
    pad = nb * blk - b * s
    flat = jnp.pad(flat, ((0, pad), (0, 0))).reshape(nb, blk, -1)
    tgt = jnp.pad(tgt, (0, pad)).reshape(nb, blk)
    mask = jnp.pad(mask, (0, pad)).reshape(nb, blk)

    @jax.checkpoint
    def nll_sum(xs, t, m):
        logits = mm("td,dv->tv", xs, head, quant)
        lz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
        return jnp.sum((lz - gold) * m)

    total = jnp.sum(jax.lax.map(lambda a: nll_sum(*a), (flat, tgt, mask)))
    return total / jnp.maximum(jnp.sum(mask), 1.0)


def loss(conf: Dict, params: Dict, mb: Dict, quant: bool = False):
    """Mean cross-entropy over the live tokens of one microbatch: the
    configuration's family computes it (``families/<family>.py``)."""
    return family(conf).loss(conf, params, mb, quant)


@functools.lru_cache(maxsize=None)
def _stats(conf_json: str, k: int, quant: bool, fault: str = ""):
    """jitted (params, batch) -> (mean loss, mean grad, mean squared grad).
    ``fault`` plants a fault in the mean of squares (``FAULTS``)."""
    conf = json.loads(conf_json)
    gfn = jax.value_and_grad(lambda p, mb: loss(conf, p, mb, quant))
    tm = jax.tree_util.tree_map

    def run(params, batch):
        mbs = tm(lambda x: x.reshape(k, x.shape[0] // k, *x.shape[1:]), batch)
        zeros = tm(jnp.zeros_like, params)

        def body(carry, xs):
            ls, gs, g2s = carry
            i, mb = xs
            lv, g = gfn(params, mb)
            # "sq_drop_one": the last microbatch's square is never added
            w = (i < k - 1).astype(jnp.float32) if fault == "sq_drop_one" else 1.0
            return (ls + lv, tm(jnp.add, gs, g), tm(lambda a, x: a + w * x * x, g2s, g)), None

        (ls, gs, g2s), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32), zeros, zeros),
                                        (jnp.arange(k), mbs))
        inv = 1.0 / k
        mean = tm(lambda x: x * inv, gs)
        sq = tm(jnp.square, mean) if fault == "sq_mean2" else tm(lambda x: x * inv, g2s)
        return ls * inv, mean, sq

    return jax.jit(run)


def lr_at(opt: Dict, step: int) -> float:
    """Linear warm-up to the peak, then cosine decay (the configured schedule)."""
    if opt.get("schedule") != "cosine" or opt.get("base_batch", 0):
        raise ValueError("the reference follows a cosine schedule with no batch rescaling only")
    peak, warm, total = float(opt["lr"]), max(int(opt["warmup_steps"]), 1), \
        max(int(opt["total_steps"]), 2)
    if step < warm:
        return peak * (step + 1) / warm
    t = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return peak * 0.5 * (1.0 + math.cos(math.pi * t))


@functools.lru_cache(maxsize=None)
def _update(opt_json: str):
    opt = json.loads(opt_json)
    b1, b2, b3 = float(opt["b1"]), float(opt["b2"]), float(opt["b3"])
    eps, wd, gamma = float(opt["eps"]), float(opt["weight_decay"]), float(opt["gamma"])
    clip, gsnr_eps = float(opt["grad_clip"]), float(opt["gsnr_eps"])

    def run(params, state, mean, sq, t, lr):
        tm = jax.tree_util.tree_map
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in mean.values()))
        g = tm(lambda x: x * jnp.minimum(1.0, clip / (gnorm + 1e-9)), mean)

        def r_of(m, s2):
            raw = jnp.square(m) / (jnp.maximum(s2 - jnp.square(m), 0.0) + gsnr_eps)
            return jnp.clip(raw / jnp.maximum(jnp.mean(raw), 1e-30), gamma, 1.0)

        r = tm(r_of, mean, sq)
        p = tm(lambda p_, r_: b3 * p_ + (1 - b3) * r_, state["p"], r)
        ghat = tm(lambda p_, g_: p_ / (1 - b3 ** t) * g_, p, g)
        m = tm(lambda m_, x: b1 * m_ + (1 - b1) * x, state["m"], ghat)
        v = tm(lambda v_, x: b2 * v_ + (1 - b2) * x * x, state["v"], ghat)

        def step(m_, v_, w):
            u = (m_ / (1 - b1 ** t)) / (jnp.sqrt(v_ / (1 - b2 ** t)) + eps) + wd * w
            wn, un = jnp.sqrt(jnp.sum(w * w)), jnp.sqrt(jnp.sum(u * u))
            ratio = jnp.where((wn > 0) & (un > 0), jnp.clip(wn, 0.0, 10.0) / (un + 1e-12), 1.0)
            return w - lr * ratio * u

        new = tm(step, m, v, params)
        return new, {"m": m, "v": v, "p": p}, {"grad": leaf_norms(g), "gsnr": leaf_norms(r)}

    return jax.jit(run, donate_argnums=(0, 1))


def half_batch(batch: Dict) -> Dict:
    """The fault "half of the batch left out": the second half of the rows
    replaced by the first, so every mean is taken over the first half."""
    def f(x):
        h = x.shape[0] // 2
        return jnp.concatenate([x[:h], x[:h]], axis=0)
    return jax.tree_util.tree_map(f, batch)


# Faults planted in the reference put in the program's place (``fault=``):
# half of the batch left out, and two faults of the mean of squares: the
# square of the mean in its place (no variance), and the last microbatch's
# square left out of the sum.
FAULTS = ("half_batch", "sq_mean2", "sq_drop_one")


def readings(conf: Dict, traffic: Dict, seed: int, batches: List[Dict],
             quant: bool = False, fault: str = "") -> Dict:
    """Run the reference through ``batches`` (host dicts) from the seed's
    weights.  Returns each step's loss, the per-tensor (per layer) norms of
    the first step's gradient as the optimizer gets it (the clipped mean,
    ``grad``) and of its GSNR r (``gsnr``), and of the parameters' change
    after the last step."""
    if fault and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    opt = traffic["optimizer"]
    with jax.default_matmul_precision("highest"):
        stats = _stats(json.dumps(conf, sort_keys=True), int(traffic["k"]), quant,
                       "" if fault == "half_batch" else fault)
        update = _update(json.dumps(opt, sort_keys=True))
        params = make_params(conf, seed)
        state = {key: jax.tree_util.tree_map(jnp.zeros_like, params) for key in ("m", "v", "p")}
        losses, first = [], {}
        for i, host in enumerate(batches):
            batch = {key: jnp.asarray(host[key]) for key in
                     ("tokens", "targets", "positions", "segments", "mask")}
            if fault == "half_batch":
                batch = half_batch(batch)
            lv, mean, sq = stats(params, batch)
            losses.append(lv)
            params, state, reads = update(params, state, mean, sq, jnp.float32(i + 1),
                                          jnp.float32(lr_at(opt, i)))
            if i == 0:
                first = {key: per_layer(v) for key, v in reads.items()}
            del mean, sq
        del state
        p0 = make_params(conf, seed)
        change = per_layer(jax.jit(lambda a, b: leaf_norms(
            {key: a[key] - b[key] for key in a}))(params, p0))
    return {"loss": [float(x) for x in jax.device_get(losses)], **first, "change": change}
