"""Plain float32 reference of a dense GQA decoder and its AdamW training step.

Imports nothing of the program.  It reads its sizes from the configuration
file's published widths, and its weights from the benchmark's own generator
(``init_state``), laid out as the program stores them:

    embed/table (V, D); blocks/pos0/{norm1,norm2}/scale (L, D);
    blocks/pos0/mixer/{q,k,v}/w (L, D, H, hd), .../b (L, H, hd);
    blocks/pos0/mixer/o/w (L, H, hd, D); blocks/pos0/ffn/{w1,w3}/w (L, D, F);
    blocks/pos0/ffn/w2/w (L, F, D); final_norm/scale (D,)

Model: token embedding; per layer x += Attn(RMSNorm(x)), x += FFN(RMSNorm(x));
a final RMSNorm; logits against the tied table.  RMSNorm multiplies by
(1 + scale), the program's parameterisation of the published weight (equal
to it at scale 0).  Attention is causal GQA (query head h reads key/value
head h // (H / H_kv)) with rotary embeddings (rotate-half, base
``rope_theta``) on q and k, and q/k/v biases where ``attention_bias``.  The
FFN is SwiGLU: silu(x W1) * (x W3) W2.  Loss: mean next-token cross-entropy
plus ``z_loss_weight`` times the mean squared log-sum-exp.

Every matmul runs in float32 at ``Precision.HIGHEST``.  Parameters are
stored in the dtypes the configuration states (bfloat16 weights, float32
norm scales) and each update is rounded to them, as the configuration
states; the moments are float32.  ``precision="fp8"`` is the control: every
matmul, forward and backward, takes operands rounded to float8 (e4m3) with
a per-tensor scale.  ``rows`` takes a subset of the batch's rows, the mean
taken over them alone: the half-batch fault.

Work is done a row at a time, layer by layer under ``jax.checkpoint``, with
attention in blocks of queries and the loss in blocks of tokens, so that a
step at 8k positions fits beside nothing else on one chip.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512  # queries per attention block
LOSS_BLOCK = 1024  # tokens per loss block
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def widths(m: dict) -> dict:
    d, hq = m["hidden_size"], m["num_attention_heads"]
    return dict(D=d, F=m["intermediate_size"], H=hq, K=m["num_key_value_heads"],
                hd=m.get("head_dim") or d // hq, L=m["num_hidden_layers"],
                V=m["vocab_size"])


# ---------------------------------------------------------------------------
# Weights and state, made on the device from the seed
# ---------------------------------------------------------------------------


def param_shapes(m: dict) -> dict:
    """{path: (shape, dtype, init law, std)} in the program's layout."""
    w = widths(m)
    D, F, H, K, hd, L, V = (w[k] for k in ("D", "F", "H", "K", "hd", "L", "V"))
    out_std = 0.02 / math.sqrt(2 * L)
    s = {"embed/table": ((V, D), "bfloat16", D ** -0.5)}
    blk = "blocks/pos0/"
    s[blk + "norm1/scale"] = ((L, D), "float32", 0.0)
    s[blk + "norm2/scale"] = ((L, D), "float32", 0.0)
    for n, heads in (("q", H), ("k", K), ("v", K)):
        s[blk + f"mixer/{n}/w"] = ((L, D, heads, hd), "bfloat16", 0.02)
        if m.get("attention_bias"):
            s[blk + f"mixer/{n}/b"] = ((L, heads, hd), "bfloat16", 0.0)
    s[blk + "mixer/o/w"] = ((L, H, hd, D), "bfloat16", out_std)
    s[blk + "ffn/w1/w"] = ((L, D, F), "bfloat16", 0.02)
    s[blk + "ffn/w3/w"] = ((L, D, F), "bfloat16", 0.02)
    s[blk + "ffn/w2/w"] = ((L, F, D), "bfloat16", out_std)
    s["final_norm/scale"] = ((D,), "float32", 0.0)
    if not m["tie_word_embeddings"]:
        s["lm_head/table"] = ((V, D), "bfloat16", D ** -0.5)
    return s


def nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        p = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, p + "/"))
        else:
            out[p] = v
    return out


def seed_key(seed: int) -> jax.Array:
    """A key from any whole seed, also one wider than 32 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def init_state(m: dict, seed: int) -> dict:
    """Train state {params, opt: {mu, nu, step}}, in one jitted call."""
    shapes = param_shapes(m)

    def make():
        key = seed_key(seed)
        params = {}
        for i, (path, (shape, dtype, std)) in enumerate(sorted(shapes.items())):
            if std == 0.0:
                params[path] = jnp.zeros(shape, dtype)
            else:
                k = jax.random.fold_in(key, i)
                params[path] = (jax.random.normal(k, shape, jnp.float32) * std
                                ).astype(dtype)
        def zeros():
            return nest({p: jnp.zeros(v.shape, jnp.float32) for p, v in params.items()})

        return {"params": nest(params),
                "opt": {"mu": zeros(), "nu": zeros(),
                        "step": jnp.zeros((), jnp.int32)}}

    return jax.jit(make)()


# ---------------------------------------------------------------------------
# Forward and loss, one row at a time
# ---------------------------------------------------------------------------


def _quant(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(FP8).astype(jnp.float32) * s


def _einsum_fp8(spec, a, b):
    @jax.custom_vjp
    def f(a, b):
        return jnp.einsum(spec, _quant(a), _quant(b), precision=HIGHEST)

    def fwd(a, b):
        return f(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        _, vjp = jax.vjp(partial(jnp.einsum, spec, precision=HIGHEST),
                         _quant(a), _quant(b))
        return vjp(_quant(g))

    f.defvjp(fwd, bwd)
    return f(a, b)


def _einsum(precision):
    if precision == "fp8":
        return _einsum_fp8
    return lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x, theta):
    S, _, hd = x.shape
    freqs = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs  # (S, hd/2)
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, ein):
    """Causal GQA attention of one row; queries in blocks, each rematerialised."""
    S, H, hd = q.shape
    G = H // k.shape[1]
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    bq = min(Q_BLOCK, S)
    nb = S // bq
    kpos = jnp.arange(S)

    @jax.checkpoint
    def block(args):
        i, qb = args
        s = ein("qhd,khd->hqk", qb, k) / math.sqrt(hd)
        qpos = i * bq + jnp.arange(bq)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return ein("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, (jnp.arange(nb), q.reshape(nb, bq, H, hd)))
    return out.reshape(S, H, hd)


def _layer(x, p, m, ein):
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    mx = p["mixer"]
    h = _rms(x, p["norm1"]["scale"], eps)

    def proj(n):
        y = ein("sd,dhk->shk", h, mx[n]["w"])
        return y + mx[n]["b"] if "b" in mx[n] else y

    q, k, v = _rope(proj("q"), theta), _rope(proj("k"), theta), proj("v")
    x = x + ein("shk,hkd->sd", _attention(q, k, v, ein), mx["o"]["w"])
    h = _rms(x, p["norm2"]["scale"], eps)
    f = p["ffn"]
    a = jax.nn.silu(ein("sd,df->sf", h, f["w1"]["w"])) * ein("sd,df->sf", h, f["w3"]["w"])
    return x + ein("sf,fd->sd", a, f["w2"]["w"])


def row_loss_sum(params, tokens, labels, m, precision):
    """Sum over one row's positions of nll + z_loss_weight * lse^2."""
    ein = _einsum(precision)
    p = params
    table = p["embed"]["table"]
    x = table[tokens]

    def body(x, lp):
        return jax.checkpoint(partial(_layer, m=m, ein=ein))(x, lp), None

    x, _ = jax.lax.scan(body, x, p["blocks"]["pos0"])
    x = _rms(x, p["final_norm"]["scale"], m["rms_norm_eps"])
    head = p["embed"]["table"] if m["tie_word_embeddings"] else p["lm_head"]["table"]
    S = x.shape[0]
    nb = S // min(LOSS_BLOCK, S)

    @jax.checkpoint
    def chunk(args):
        xc, yc = args
        logits = ein("td,vd->tv", xc, head)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yc[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold) + m["z_loss_weight"] * jnp.sum(lse * lse)

    parts = jax.lax.map(chunk, (x.reshape(nb, -1, x.shape[-1]), labels.reshape(nb, -1)))
    return jnp.sum(parts)


# ---------------------------------------------------------------------------
# AdamW, as the configuration's optimizer states it
# ---------------------------------------------------------------------------


def lr_at(opt: dict, step):
    step = step.astype(jnp.float32)
    warm = step / max(1.0, opt["warmup_steps"])
    frac = (step - opt["warmup_steps"]) / max(1.0, opt["total_steps"] - opt["warmup_steps"])
    frac = jnp.clip(frac, 0.0, 1.0)
    cos = opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * 0.5 * (1 + jnp.cos(jnp.pi * frac))
    return opt["peak_lr"] * jnp.where(step < opt["warmup_steps"], warm, cos)


def decays(path: str) -> bool:
    """Weight decay reaches the weight matrices and tables, not biases or norms."""
    return path.endswith("/w") or path.endswith("/table")


def adamw(params, grads, mu, nu, step, opt):
    """One AdamW step on flat dicts; returns (params, mu, nu, clipped grads)."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
    clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    step = step + 1
    lr = lr_at(opt, step)
    bc1 = 1 - opt["b1"] ** step.astype(jnp.float32)
    bc2 = 1 - opt["b2"] ** step.astype(jnp.float32)
    out_p, out_mu, out_nu, seen = {}, {}, {}, {}
    for k, p in params.items():
        g = grads[k] * clip
        seen[k] = g
        out_mu[k] = opt["b1"] * mu[k] + (1 - opt["b1"]) * g
        out_nu[k] = opt["b2"] * nu[k] + (1 - opt["b2"]) * g * g
        delta = (out_mu[k] / bc1) / (jnp.sqrt(out_nu[k] / bc2) + opt["eps"])
        p32 = p.astype(jnp.float32)
        if opt["weight_decay"] and decays(k):
            delta = delta + opt["weight_decay"] * p32
        out_p[k] = (p32 - lr * delta).astype(p.dtype)
    return out_p, out_mu, out_nu, seen


# ---------------------------------------------------------------------------
# Readings: what the comparison needs of the checked steps
# ---------------------------------------------------------------------------


def leaf_norms(flat: dict) -> dict:
    """Norm of each leaf, a stacked (blocks/...) leaf per layer."""
    out = {}
    for k, v in flat.items():
        v = v.astype(jnp.float32)
        if k.startswith("blocks/"):
            out[k] = jnp.sqrt(jnp.sum(v * v, axis=tuple(range(1, v.ndim))))
        else:
            out[k] = jnp.sqrt(jnp.sum(v * v))
    return out


def run_steps(m: dict, opt: dict, params: dict, batches: list, *,
              precision: str = "f32", rows=None) -> dict:
    """Readings of len(batches) reference steps from ``params`` (a nested tree).

    Returns {"losses": [...], "grad": {leaf: norm(s)} of the first clipped
    gradient, "change": {leaf: norm(s)} of the parameters' change after the
    last step}.
    """
    grad_row = jax.jit(jax.value_and_grad(
        partial(row_loss_sum, m=m, precision=precision)))
    step_fn = jax.jit(partial(adamw, opt=opt), donate_argnums=(2, 3))
    norms = jax.jit(leaf_norms)
    diff_norms = jax.jit(lambda a, b: leaf_norms(
        {k: a[k].astype(jnp.float32) - b[k].astype(jnp.float32) for k in a}))

    p0 = flatten(params)
    p = dict(p0)
    mu = {k: jnp.zeros(v.shape, jnp.float32) for k, v in p.items()}
    nu = {k: jnp.zeros(v.shape, jnp.float32) for k, v in p.items()}
    step = jnp.zeros((), jnp.int32)
    losses, first = [], None
    for batch in batches:
        toks, labs = np.asarray(batch["tokens"]), np.asarray(batch["labels"])
        use = range(toks.shape[0]) if rows is None else rows
        T = len(use) * toks.shape[1]
        total, grads = 0.0, None
        tree = nest({k: v.astype(jnp.float32) for k, v in p.items()})
        for r in use:
            val, g = grad_row(tree, jnp.asarray(toks[r]), jnp.asarray(labs[r]))
            g = flatten(g)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
            total += float(val)
        del tree
        grads = {k: v / T for k, v in grads.items()}
        p, mu, nu, seen = step_fn(p, grads, mu, nu, step)
        step = step + 1
        losses.append(total / T)
        if first is None:
            first = jax.device_get(norms(seen))
        del grads, seen
    change = jax.device_get(diff_norms(p, p0))
    return {"losses": losses, "grad": first, "change": change}
