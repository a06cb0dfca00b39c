"""Plain reference of a decoder-only LM fine-tuning step, in float32.

Written from the published architecture (Llama-style trunk: RMSNorm,
GQA attention with rotate-half RoPE and an optional sliding window,
SwiGLU MLP or a token-choice top-k MoE), with nothing imported from the
program.  It reads a configuration file's published keys and the
program's checkpoint leaf names (``embed``, ``final_norm/scale``,
``lm_head``, and per-layer ``scan_0/...`` leaves stacked on a leading
layer axis), so it can be handed the same seeded weights.

What it follows and where:

- The configuration is the file's published values with its
  ``departures`` applied: each names a published key and the value the
  cell runs (:func:`as_run`).
- RMSNorm gains are stored as ``g - 1`` (the checkpoint format); the
  epsilon is the configuration's ``rms_norm_eps``.
- Granite's scalars (``embedding_multiplier``, ``attention_multiplier``,
  ``residual_multiplier``, ``logits_scaling``) apply when the file gives
  them; without them the embedding, residual and logits are unscaled and
  the attention scale is ``head_dim ** -0.5``.
- The MoE routes each token to its ``num_experts_per_tok`` largest
  softmax router probabilities, renormalized, with the capacity the
  configuration's ``assumed`` routing states: tokens are cut into groups
  of ``moe_group_tokens`` in (batch, position) order, and an expert takes
  at most ``moe_capacity`` of a group's assignments, rank 0 first, then
  rank 1, ..., each rank in token order; what is over capacity is
  dropped.  The loss adds the Switch load-balance term and the router
  z-loss with the file's coefficients.
- The loss is the mean next-token cross entropy over the vocabulary the
  configuration states; logits of padding rows are never formed.
- The optimizer is AdamW as the traffic file states it: global-norm
  clipping, bias-corrected moments, decoupled weight decay on every leaf,
  linear warmup then cosine decay to a tenth.  Steps may start from a
  carried optimizer state (moments and step count), as a federated
  client's second and later rounds do.

Every matmul runs at ``precision="highest"``.  ``precision="float8"`` is
the control, float8 in both passes as the program is bfloat16 in both:
each matmul's operands are rounded to float8 e4m3, and the cotangents of
its result and of its operands to float8 e5m2, each tensor under its own
scale (its largest magnitude mapped to the format's largest value).
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import numpy as np

import jax
import jax.numpy as jnp

QCHUNK = 1024          # elements per int8 scale of the q8 wire codec


# ---------------------------------------------------------------------------
# q8 wire codec, as its format is documented: per 1024-element window of
# the flat fp32 vector (leaves concatenated in checkpoint order), scale
# max|x| / 127 (1 for an all-zero window), q = clip(rint(x / scale)),
# decoded value fp32(q * scale)
# ---------------------------------------------------------------------------
def flat(leaves: Sequence[np.ndarray]) -> np.ndarray:
    return np.concatenate([np.asarray(a, np.float32).reshape(-1)
                           for a in leaves])


def window_scales(x: np.ndarray) -> np.ndarray:
    """Each window's scale ``max|x| / 127`` (1 for an all-zero window)."""
    n = x.size
    pad = np.zeros(-(-n // QCHUNK) * QCHUNK, np.float32)
    pad[:n] = x
    scale = (np.abs(pad.reshape(-1, QCHUNK)).max(axis=1)
             / np.float32(127.0)).astype(np.float32)
    scale[scale == 0] = np.float32(1.0)
    return scale


def q8_encode_flat(x: np.ndarray):
    """The flat fp32 vector ``x`` as q8: ``(int8 values, window scales)``."""
    n = x.size
    scale = window_scales(x)
    pad = np.zeros(scale.size * QCHUNK, np.float32)
    pad[:n] = x
    win = pad.reshape(-1, QCHUNK)
    q = np.clip(np.rint(win / scale[:, None]), -127, 127).astype(np.int8)
    return q.reshape(-1)[:n], scale


def q8_decode_flat(x: np.ndarray) -> np.ndarray:
    """The flat fp32 vector ``x`` through the q8 codec and back."""
    n = x.size
    q, scale = q8_encode_flat(x)
    pad = np.zeros(scale.size * QCHUNK, np.float64)
    pad[:n] = q
    return (pad.reshape(-1, QCHUNK) * scale[:, None]).astype(
        np.float32).reshape(-1)[:n]


def q8_roundtrip(leaves: Sequence[np.ndarray]) -> List[np.ndarray]:
    dec = q8_decode_flat(flat(leaves))
    out, off = [], 0
    for a in leaves:
        out.append(dec[off:off + a.size].reshape(a.shape))
        off += a.size
    return out


def as_run(cfg: Dict) -> Dict:
    """The configuration as the cell runs it: the published values with
    each ``departures`` entry's ``runs`` value in place."""
    dep = cfg.get("departures") or {}
    return {**cfg, **{k: v["runs"] for k, v in dep.items()}}


def leaf_shapes(cfg: Dict) -> Dict[str, tuple]:
    """The checkpoint leaves this configuration has, by name.  Vocabulary
    rows are padded to a multiple of 256; the padding never holds a
    token and its logits are never formed."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    f, E = cfg["intermediate_size"], cfg.get("num_local_experts") or 0
    Vp = -(-cfg["vocab_size"] // 256) * 256
    out = {"embed": (Vp, d), "final_norm/scale": (d,),
           "scan_0/norm1/scale": (L, d), "scan_0/norm2/scale": (L, d),
           "scan_0/mix/wq": (L, d, H, hd), "scan_0/mix/wk": (L, d, KV, hd),
           "scan_0/mix/wv": (L, d, KV, hd), "scan_0/mix/wo": (L, H, hd, d)}
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = (d, Vp)
    if E:
        out.update({"scan_0/mlp/router": (L, d, E),
                    "scan_0/mlp/wi_gate": (L, E, d, f),
                    "scan_0/mlp/wi_up": (L, E, d, f),
                    "scan_0/mlp/wo": (L, E, f, d)})
    else:
        out.update({"scan_0/mlp/wi_gate": (L, d, f),
                    "scan_0/mlp/wi_up": (L, d, f),
                    "scan_0/mlp/wo": (L, f, d)})
    return out


# ---------------------------------------------------------------------------
# matmuls at the chosen precision
# ---------------------------------------------------------------------------
def _round(x, dtype):
    """``x`` rounded to a float8 ``dtype`` under a per-tensor scale that
    maps its largest magnitude to the format's largest value."""
    top = float(jnp.finfo(dtype).max)
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / top, 1.0)
    return jnp.clip(x / s, -top, top).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _fp8_operand(x):
    return _round(x, jnp.float8_e4m3fn)


_fp8_operand.defvjp(lambda x: (_round(x, jnp.float8_e4m3fn), None),
                    lambda _, g: (_round(g, jnp.float8_e5m2),))


@jax.custom_vjp
def _fp8_result(y):
    return y


_fp8_result.defvjp(lambda y: (y, None),
                   lambda _, g: (_round(g, jnp.float8_e5m2),))


def _einsum(precision: str):
    if precision == "float32":
        return lambda eq, a, b: jnp.einsum(eq, a, b, precision="highest")
    if precision == "float8":
        return lambda eq, a, b: _fp8_result(jnp.einsum(
            eq, _fp8_operand(a), _fp8_operand(b), precision="highest"))
    raise ValueError(f"unknown reference precision {precision!r}")


def _rms(x, g_minus_1, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + g_minus_1)


def _rope(x, theta):
    """Rotate-half RoPE over positions 0..S-1; x: (B, S, heads, hd)."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / theta ** (np.arange(half, dtype=np.float64) / half)
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(w, l, x, cfg, mm):
    B, S, _ = x.shape
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    q = _rope(mm("bsd,dnh->bsnh", x, w["scan_0/mix/wq"][l]),
              cfg["rope_theta"])
    k = _rope(mm("bsd,dnh->bsnh", x, w["scan_0/mix/wk"][l]),
              cfg["rope_theta"])
    v = mm("bsd,dnh->bsnh", x, w["scan_0/mix/wv"][l])
    q = q * cfg.get("attention_multiplier", hd ** -0.5)
    # query head n reads kv head n // (H / KV)
    q = q.reshape(B, S, KV, H // KV, hd)
    s = mm("bqkgh,bskh->bkgqs", q, k)
    qpos, kpos = np.arange(S)[:, None], np.arange(S)[None, :]
    allowed = kpos <= qpos
    if cfg.get("sliding_window"):
        allowed &= kpos > qpos - cfg["sliding_window"]
    s = jnp.where(jnp.asarray(allowed), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    ctx = mm("bkgqs,bskh->bqkgh", p, v).reshape(B, S, H, hd)
    return mm("bsnh,nhd->bsd", ctx, w["scan_0/mix/wo"][l])


def _dense_mlp(w, l, x, mm):
    g = mm("bsd,df->bsf", x, w["scan_0/mlp/wi_gate"][l])
    u = mm("bsd,df->bsf", x, w["scan_0/mlp/wi_up"][l])
    return mm("bsf,fd->bsd", jax.nn.silu(g) * u, w["scan_0/mlp/wo"][l])


def _moe_mlp(w, l, x, cfg, mm):
    """Returns (y, aux loss of this layer)."""
    B, S, d = x.shape
    E, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    T = B * S
    Tg, C = cfg["assumed"]["moe_group_tokens"], cfg["assumed"]["moe_capacity"]
    xt = x.reshape(T, d)
    logits = mm("td,de->te", xt, w["scan_0/mlp/router"][l])
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)                       # (T, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    # capacity: rank by rank, token by token inside each group
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)        # (T, k, E)
    og = onehot.reshape(T // Tg, Tg, k, E)
    taken = jnp.zeros((T // Tg, 1, E), jnp.float32)
    keep = []
    for r in range(k):
        before = jnp.cumsum(og[:, :, r], axis=1) - og[:, :, r] + taken
        keep.append(jnp.sum(before * og[:, :, r], axis=-1) < C)
        taken = taken + jnp.sum(og[:, :, r], axis=1, keepdims=True)
    keep = jnp.stack(keep, axis=-1).reshape(T, k)
    combine = jnp.einsum("tk,tke->te", gate * keep, onehot)
    # every expert on every token, then the routed combination
    g = mm("td,edf->tef", xt, w["scan_0/mlp/wi_gate"][l])
    u = mm("td,edf->tef", xt, w["scan_0/mlp/wi_up"][l])
    eo = mm("tef,efd->ted", jax.nn.silu(g) * u, w["scan_0/mlp/wo"][l])
    y = jnp.einsum("te,ted->td", combine, eo,
                   precision="highest").reshape(B, S, d)
    density = jnp.mean(probs, axis=0)
    usage = jnp.mean(jnp.sum(onehot, axis=1), axis=0)
    aux = (cfg["router_aux_loss_coef"] * E * jnp.sum(density * usage)
           + cfg["router_z_loss_coef"]
           * jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2))
    return y, aux


def _losses(w: Dict, batch: Dict, cfg: Dict, precision: str):
    """(mean next-token cross entropy, the MoE router losses)."""
    cfg = as_run(cfg)
    mm = _einsum(precision)
    eps = cfg["rms_norm_eps"]
    res = cfg.get("residual_multiplier", 1.0)
    x = w["embed"][batch["tokens"]] * cfg.get("embedding_multiplier", 1.0)
    aux = 0.0
    for l in range(cfg["num_hidden_layers"]):
        h = _rms(x, w["scan_0/norm1/scale"][l], eps)
        x = x + res * _attention(w, l, h, cfg, mm)
        h = _rms(x, w["scan_0/norm2/scale"][l], eps)
        if cfg.get("num_local_experts"):
            y, a = _moe_mlp(w, l, h, cfg, mm)
            aux = aux + a
        else:
            y = _dense_mlp(w, l, h, mm)
        x = x + res * y
    x = _rms(x, w["final_norm/scale"], eps)
    V = cfg["vocab_size"]
    head = (w["embed"][:V].T if cfg["tie_word_embeddings"]
            else w["lm_head"][:, :V])
    logits = mm("bsd,dv->bsv", x, head) / cfg.get("logits_scaling", 1.0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None],
                               axis=-1)[..., 0]
    return jnp.mean(logz - gold), aux


def loss_fn(w: Dict, batch: Dict, cfg: Dict, precision: str = "float32"):
    """The training loss: cross entropy plus the MoE router losses."""
    ce, aux = _losses(w, batch, cfg, precision)
    return ce + aux


def eval_loss(w: Dict, batch: Dict, cfg: Dict,
              precision: str = "float32") -> float:
    """The evaluation loss: the mean cross entropy alone."""
    with jax.default_matmul_precision("highest"):
        ce, _ = jax.jit(lambda w_, b_: _losses(w_, b_, cfg, precision))(
            {p: jnp.asarray(a, jnp.float32) for p, a in w.items()},
            {k: jnp.asarray(x) for k, x in batch.items()})
    return float(ce)


def lr_at(step: int, opt: Dict) -> float:
    """Linear warmup to ``learning_rate``, then cosine decay to a tenth
    over ``total_steps - warmup_steps``; ``step`` counts from 0."""
    lr, warm, total = opt["learning_rate"], opt["warmup_steps"], \
        opt["total_steps"]
    if step < warm:
        return lr * (step + 1) / max(warm, 1)
    frac = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return lr * (0.1 + 0.9 * 0.5 * (1.0 + math.cos(math.pi * frac)))


def fit_steps(w0: Dict, batches: Sequence[Dict], cfg: Dict, opt: Dict,
              precision: str = "float32", state: Dict = None) -> Dict:
    """AdamW steps from ``w0`` on ``batches``, from the optimizer state
    ``state`` (``{"mu": {leaf: array}, "nu": {...}, "step": int}``, the
    steps already taken) or from a fresh one.  Returns each step's loss,
    the per-leaf norms of the first step's clipped gradient (what the
    optimizer receives) and the per-leaf norms of the total change of
    the weights."""
    b1, b2, eps, wd = opt["beta1"], opt["beta2"], opt["eps"], \
        opt["weight_decay"]

    def norms(tree):
        return {p: jnp.sqrt(jnp.sum(x * x)) for p, x in tree.items()}

    @jax.jit
    def step(w, m, v, batch, t, lr):
        with jax.default_matmul_precision("highest"):
            loss, g = jax.value_and_grad(
                lambda w_: loss_fn(w_, batch, cfg, precision))(w)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
        g = {p: x * jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
             for p, x in g.items()}
        m = {p: b1 * m[p] + (1 - b1) * g[p] for p in w}
        v = {p: b2 * v[p] + (1 - b2) * g[p] * g[p] for p in w}
        w = {p: w[p] - lr * ((m[p] / (1 - b1 ** t))
                             / (jnp.sqrt(v[p] / (1 - b2 ** t)) + eps)
                             + wd * w[p]) for p in w}
        return w, m, v, loss, norms(g)

    w = {p: jnp.asarray(a, jnp.float32) for p, a in w0.items()}
    start = dict(w)
    if state is None:
        m = {p: jnp.zeros_like(a) for p, a in w.items()}
        v = {p: jnp.zeros_like(a) for p, a in w.items()}
        t0 = 0
    else:
        m = {p: jnp.asarray(state["mu"][p], jnp.float32) for p in w}
        v = {p: jnp.asarray(state["nu"][p], jnp.float32) for p in w}
        t0 = int(state["step"])
    losses, first = [], None
    for t, batch in enumerate(batches, start=t0 + 1):
        w, m, v, loss, gn = step(w, m, v, {k: jnp.asarray(x) for k, x in
                                           batch.items()},
                                 jnp.float32(t), jnp.float32(lr_at(t - 1,
                                                                   opt)))
        losses.append(float(loss))
        if first is None:
            first = {p: float(x) for p, x in gn.items()}
    change = jax.jit(lambda a, b: norms({p: a[p] - b[p] for p in a}))(
        w, start)
    return {"losses": losses, "grad_norms": first,
            "change_norms": {p: float(x) for p, x in change.items()}}
