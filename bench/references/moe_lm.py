"""Plain float32 reference of a DeepSeekMoE decoder on one chip's expert share,
and its AdamW training step.

Imports nothing of the program; the pieces it shares with the dense
reference (RMSNorm, rotary embeddings, blocked causal attention, the
float8 control's matmul, AdamW's schedule, flattening and leaf norms) are
``decoder_lm``'s, and the widths' short names ``benchlib.moe_flops``'s.  It
reads its sizes from the configuration file's published widths and the
share (``n_routed_experts`` experts held here, from ``held_first_expert``,
out of the router's ``router_experts``), and its weights from the
benchmark's own generator (``init_state``), laid out as the program stores
them:

    embed/table, lm_head/table (V, D); final_norm/scale (D,)
    head0/{norm1,norm2}/scale (D,); head0/mixer/{q,k,v}/w (D, H, hd);
    head0/mixer/o/w (H, hd, D); head0/ffn/{w1,w3}/w (D, F); head0/ffn/w2/w (F, D)
    blocks/pos0/...: the same attention and norms stacked over the L MoE
    layers, and ffn/router/w (L, D, E); ffn/{w1,w3} (L, N, D, Fe);
    ffn/w2 (L, N, Fe, D); ffn/shared/{w1,w3}/w (L, D, Fs); ffn/shared/w2/w (L, Fs, D)

Model: token embedding; ``first_k_dense_replace`` dense layers, then MoE
layers; each layer x += Attn(RMSNorm(x)), x += FFN(RMSNorm(x)); a final
RMSNorm; logits against the untied head.  Attention is causal multi-head
attention with rotary embeddings, as in ``decoder_lm``.  A dense FFN is
SwiGLU of width ``intermediate_size``.  An MoE FFN:

* the router's logits over all E experts, in float32 from the layer's own
  input; softmax; the top ``num_experts_per_tok`` probabilities are the
  gates, renormalised only where ``norm_topk_prob``;
* every token goes through every expert held here, a SwiGLU of width
  ``moe_intermediate_size``, and each output is weighted by the token's gate
  for that expert, 0 where the expert was not chosen (a mask, no grouped
  matmul); experts held elsewhere add nothing;
* the shared experts, one SwiGLU of width ``n_shared_experts`` times the
  expert width, on every token.

Loss: mean next-token cross-entropy, plus ``z_loss_weight`` times the mean
squared log-sum-exp, plus for each MoE layer ``aux_loss_alpha`` times the
sequence-level balance loss, the mean over rows of sum_e f_e P_e, where
f_e = E / (S K) times the row's (token, choice) pairs on expert e and P_e is
the row's mean router probability of e.  The router has no z-loss.

Every matmul runs in float32 at ``Precision.HIGHEST``; parameters are stored
in the dtypes the configuration states and each update is rounded to them.
``precision="fp8"`` is the control: every matmul takes operands rounded to
float8 (e4m3), router included.  ``rows`` takes a subset of the batch's
rows: the half-batch fault.

Departures, for memory: work is done a row at a time, layer by layer under
``jax.checkpoint``; and between steps the Adam moments are kept on the host
(a float32 copy of the weights, the float32 gradient and two float32
moments do not fit one chip beside the weights), each leaf's moments sent
to the device for its update alone.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import load
from benchlib.moe_flops import widths

dl = load("references", "decoder_lm")
flatten, nest, leaf_norms, seed_key = dl.flatten, dl.nest, dl.leaf_norms, dl.seed_key


# ---------------------------------------------------------------------------
# Weights and state, made on the device from the seed
# ---------------------------------------------------------------------------


def param_shapes(m: dict) -> dict:
    """{path: (shape, dtype, std)} in the program's layout."""
    w = widths(m)
    D, H, K, hd, L, V = (w[k] for k in ("D", "H", "K", "hd", "L", "V"))
    n_layers = m["num_hidden_layers"]
    out_std = 0.02 / math.sqrt(2 * n_layers)
    s = {"embed/table": ((V, D), "bfloat16", D ** -0.5),
         "lm_head/table": ((V, D), "bfloat16", D ** -0.5),
         "final_norm/scale": ((D,), "float32", 0.0)}

    def attention(prefix, lead):
        s[prefix + "norm1/scale"] = (lead + (D,), "float32", 0.0)
        s[prefix + "norm2/scale"] = (lead + (D,), "float32", 0.0)
        for n, heads in (("q", H), ("k", K), ("v", K)):
            s[prefix + f"mixer/{n}/w"] = (lead + (D, heads, hd), "bfloat16", 0.02)
        s[prefix + "mixer/o/w"] = (lead + (H, hd, D), "bfloat16", out_std)

    def swiglu(prefix, lead, width):
        s[prefix + "w1/w"] = (lead + (D, width), "bfloat16", 0.02)
        s[prefix + "w3/w"] = (lead + (D, width), "bfloat16", 0.02)
        s[prefix + "w2/w"] = (lead + (width, D), "bfloat16", out_std)

    for i in range(w["dense"]):
        attention(f"head{i}/", ())
        swiglu(f"head{i}/ffn/", (), w["F"])
    blk = "blocks/pos0/"
    attention(blk, (L,))
    s[blk + "ffn/router/w"] = ((L, D, w["E"]), "bfloat16", 0.02)
    s[blk + "ffn/w1"] = ((L, w["N"], D, w["Fe"]), "bfloat16", 0.02)
    s[blk + "ffn/w3"] = ((L, w["N"], D, w["Fe"]), "bfloat16", 0.02)
    s[blk + "ffn/w2"] = ((L, w["N"], w["Fe"], D), "bfloat16", out_std)
    swiglu(blk + "ffn/shared/", (L,), w["Fs"])
    return s


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


def _attention_block(x, p, m, ein):
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    mx = p["mixer"]
    h = dl._rms(x, p["norm1"]["scale"], eps)
    q = dl._rope(ein("sd,dhk->shk", h, mx["q"]["w"]), theta)
    k = dl._rope(ein("sd,dhk->shk", h, mx["k"]["w"]), theta)
    v = ein("sd,dhk->shk", h, mx["v"]["w"])
    return x + ein("shk,hkd->sd", dl._attention(q, k, v, ein), mx["o"]["w"])


def _swiglu(h, f, ein):
    a = jax.nn.silu(ein("sd,df->sf", h, f["w1"]["w"])) * ein("sd,df->sf", h, f["w3"]["w"])
    return ein("sf,fd->sd", a, f["w2"]["w"])


def _dense_layer(x, p, m, ein):
    x = _attention_block(x, p, m, ein)
    return x + _swiglu(dl._rms(x, p["norm2"]["scale"], m["rms_norm_eps"]), p["ffn"], ein)


def route(h, router_w, m, ein):
    """(probs (S, E), gates (S, K), choices (S, K)) of one row."""
    probs = jax.nn.softmax(ein("sd,de->se", h, router_w), axis=-1)
    gates, idx = jax.lax.top_k(probs, m["num_experts_per_tok"])
    if m["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return probs, gates, idx


def moe_ffn(h, f, m, ein):
    """(y, balance loss, pairs on held experts) of one row's MoE FFN."""
    w = widths(m)
    E, N, first = w["E"], w["N"], m["held_first_expert"]
    probs, gates, idx = route(h, f["router"]["w"], m, ein)
    chosen = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # (S, K, E)
    gate = jnp.einsum("ske,sk->se", chosen, gates)[:, first:first + N]  # (S, N)
    a = jax.nn.silu(ein("sd,ndf->snf", h, f["w1"])) * ein("sd,ndf->snf", h, f["w3"])
    y = ein("snf,nfd->sd", a * gate[..., None], f["w2"]) + _swiglu(h, f["shared"], ein)
    S, K = idx.shape
    frac = jnp.sum(chosen, axis=(0, 1)) * (E / (S * K))
    balance = jnp.sum(frac * jnp.mean(probs, axis=0))
    held = jnp.sum(chosen[..., first:first + N])
    return y, balance, held


def _moe_layer(x, p, m, ein):
    """(x, balance loss of the row, pairs on held experts) of one MoE layer."""
    x = _attention_block(x, p, m, ein)
    y, balance, held = moe_ffn(dl._rms(x, p["norm2"]["scale"], m["rms_norm_eps"]),
                               p["ffn"], m, ein)
    return x + y, balance, held


def row_loss_sum(params, tokens, labels, m, precision):
    """(sum over one row's positions of nll + z_loss_weight * lse^2, plus the
    row's share of the balance losses; the row's pairs on held experts)."""
    ein = dl._einsum(precision)
    p = params
    x = p["embed"]["table"][tokens]
    for i in range(m["first_k_dense_replace"]):
        x = jax.checkpoint(partial(_dense_layer, m=m, ein=ein))(x, p[f"head{i}"])

    def body(carry, lp):
        x, bal, held = carry
        x, b, n = jax.checkpoint(partial(_moe_layer, m=m, ein=ein))(x, lp)
        return (x, bal + b, held + n), None

    zero = jnp.zeros((), jnp.float32)
    (x, bal, held), _ = jax.lax.scan(body, (x, zero, zero), p["blocks"]["pos0"])
    x = dl._rms(x, p["final_norm"]["scale"], m["rms_norm_eps"])
    head = p["lm_head"]["table"]
    S = x.shape[0]
    nb = S // min(dl.LOSS_BLOCK, S)

    @jax.checkpoint
    def chunk(args):
        xc, yc = args
        logits = ein("td,vd->tv", xc, head)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yc[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold) + m["z_loss_weight"] * jnp.sum(lse * lse)

    parts = jax.lax.map(chunk, (x.reshape(nb, -1, x.shape[-1]), labels.reshape(nb, -1)))
    # the loss is a mean over rows of each row's balance: S positions carry it
    return jnp.sum(parts) + S * m["aux_loss_alpha"] * bal, held


# ---------------------------------------------------------------------------
# AdamW, as the configuration's optimizer states it, a leaf at a time
# ---------------------------------------------------------------------------


def decays(path: str) -> bool:
    """Weight decay reaches the weight matrices and tables, not the norms."""
    return not path.endswith("/scale")


def _adamw_leaf(p, g, mu, nu, step, clip, opt, decay):
    g = g * clip
    lr = dl.lr_at(opt, step)
    bc1 = 1 - opt["b1"] ** step.astype(jnp.float32)
    bc2 = 1 - opt["b2"] ** step.astype(jnp.float32)
    mu = opt["b1"] * mu + (1 - opt["b1"]) * g
    nu = opt["b2"] * nu + (1 - opt["b2"]) * g * g
    delta = (mu / bc1) / (jnp.sqrt(nu / bc2) + opt["eps"])
    p32 = p.astype(jnp.float32)
    if decay and opt["weight_decay"]:
        delta = delta + opt["weight_decay"] * p32
    return (p32 - lr * delta).astype(p.dtype), mu, nu, g


# ---------------------------------------------------------------------------
# Readings: what the comparison needs of the checked steps
# ---------------------------------------------------------------------------


def run_steps(m: dict, opt: dict, params: dict, batches: list, *,
              precision: str = "f32", rows=None) -> dict:
    """Readings of len(batches) reference steps from ``params`` (a nested tree).

    Returns {"losses": [...], "grad": {leaf: norm(s)} of the first clipped
    gradient, "change": {leaf: norm(s)} of the parameters' change after the
    last step, "rows": [the pairs on held experts, over the MoE layers, of
    each step's batch]}.
    """
    with jax.default_matmul_precision("highest"):
        return _run_steps(m, opt, params, batches, precision, rows)


def _run_steps(m, opt, params, batches, precision, rows):
    vg = jax.value_and_grad(partial(row_loss_sum, m=m, precision=precision), has_aux=True)

    @partial(jax.jit, donate_argnums=(3,))
    def grad_row(p, toks, labs, acc):
        tree = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        (val, held), g = vg(tree, toks, labs)
        return val, held, jax.tree.map(jnp.add, acc, g)

    sq_norm = jax.jit(lambda g: sum(jnp.sum(v * v) for v in jax.tree.leaves(g)))
    leaf_step = {d: jax.jit(partial(_adamw_leaf, opt=opt, decay=d), donate_argnums=(0, 2, 3))
                 for d in (False, True)}
    leaf_norm = jax.jit(lambda k, v: leaf_norms({k: v})[k], static_argnums=0)
    diff_norm = jax.jit(lambda k, a, b: leaf_norms(
        {k: a.astype(jnp.float32) - b.astype(jnp.float32)})[k], static_argnums=0)

    p = flatten(params)  # updated in place: the caller's arrays are given up
    p0 = jax.device_get(p)
    mu = {k: np.zeros(v.shape, np.float32) for k, v in p.items()}
    nu = {k: np.zeros(v.shape, np.float32) for k, v in p.items()}
    step = jnp.zeros((), jnp.int32)
    losses, counts, first = [], [], None
    for batch in batches:
        toks, labs = np.asarray(batch["tokens"]), np.asarray(batch["labels"])
        use = range(toks.shape[0]) if rows is None else rows
        T = len(use) * toks.shape[1]
        tree = nest(p)
        acc = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), tree)
        total, held = 0.0, 0.0
        for r in use:
            val, n, acc = grad_row(tree, jnp.asarray(toks[r]), jnp.asarray(labs[r]), acc)
            total += float(val)
            held += float(n)
        grads = {k: v / T for k, v in flatten(acc).items()}
        del acc, tree
        gnorm = math.sqrt(float(sq_norm(grads)))
        clip = jnp.float32(min(1.0, opt["grad_clip"] / max(gnorm, 1e-9)))
        step = step + 1
        seen = {}
        for k in sorted(p):
            p[k], mu_k, nu_k, g = leaf_step[decays(k)](
                p[k], grads.pop(k), jnp.asarray(mu[k]), jnp.asarray(nu[k]), step, clip)
            mu[k], nu[k] = np.asarray(mu_k), np.asarray(nu_k)
            if first is None:
                seen[k] = np.asarray(leaf_norm(k, g))
            del mu_k, nu_k, g
        losses.append(total / T)
        counts.append(held)
        if first is None:
            first = seen
    change = {k: np.asarray(diff_norm(k, p[k], jnp.asarray(p0[k]))) for k in p}
    return {"losses": losses, "grad": first, "change": change, "rows": counts}
