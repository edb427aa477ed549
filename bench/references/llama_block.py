"""Plain reference of a pre-norm decoder block with rotary attention.

The block as the benchmark's configurations publish it (Llama-style, and
Qwen3 with ``qk_norm``): RMSNorm, grouped-query attention with rotary
positions (half-split rotation), an optional per-head RMSNorm on q and k
before the rotation, a gated SiLU MLP, a final RMSNorm and an untied
output head. Written in plain ``jax.numpy``, with float32 arithmetic at
``highest`` matmul precision; it imports nothing of the system under
test. At ``bf16`` it computes at the precision a bfloat16 configuration
states, as a plain bfloat16 model does: every matmul, norm, rotation,
attention output, gated product and residual sum is rounded to bfloat16,
and everything inside one of them (accumulation, norm statistics,
softmax) stays float32.

This file also owns the weights. ``program_params`` builds them, from the
seed, in the layout the served model takes (layers stacked on a leading
axis, bfloat16), in one jitted call on the device. ``layer_params``
builds one layer's share again from the same seed, so the reference never
reads what the program holds and can run after the program is freed. The
values are exact in bfloat16 (a 16-bit integer times a power of two,
rounded once), so both builds give the same bits on any backend.
"""
from __future__ import annotations

import math
import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512  # query rows per attention block (bounds the score tile)


def _leaves(model: dict) -> list[tuple[tuple[str, ...], tuple[int, ...], str]]:
    """(path, shape of one layer's leaf or of a global leaf, kind) in the
    served layout; kind is 'dense' (fan-in scaled), 'norm' or 'embed'."""
    D, F, V = model["d_model"], model["d_ff"], model["vocab"]
    H, Hkv, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    layer = [
        (("layers", "ln1"), (D,), "norm"),
        (("layers", "attn", "wq"), (D, H * hd), "dense"),
        (("layers", "attn", "wk"), (D, Hkv * hd), "dense"),
        (("layers", "attn", "wv"), (D, Hkv * hd), "dense"),
        (("layers", "attn", "wo"), (H * hd, D), "dense"),
        (("layers", "ln2"), (D,), "norm"),
        (("layers", "mlp", "w_gate"), (D, F), "dense"),
        (("layers", "mlp", "w_up"), (D, F), "dense"),
        (("layers", "mlp", "w_down"), (F, D), "dense"),
    ]
    if model["qk_norm"]:
        layer += [(("layers", "attn", "q_norm"), (hd,), "norm"),
                  (("layers", "attn", "k_norm"), (hd,), "norm")]
    return layer + [
        (("embed",), (V, D), "embed"),
        (("final_ln",), (D,), "norm"),
        (("head",), (D, V), "dense"),
    ]


def base_key(seed: int):
    """A key from the whole seed: both 32-bit halves count."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _value(key, path, shape, kind, layer):
    """One leaf (of one layer): a uniform draw of 16-bit integers times a
    power of two, rounded once to bfloat16."""
    key = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()))
    key = jax.random.fold_in(key, layer)
    v = jax.lax.bitcast_convert_type(
        jax.random.bits(key, shape, jnp.uint16), jnp.int16)
    v = jnp.where(v == -32768, 0, v).astype(jnp.float32)  # symmetric
    if kind == "norm":  # weights in [0.875, 1.125]
        return (1.0 + v * 2.0 ** -18).astype(jnp.bfloat16)
    if kind == "embed":  # std about 0.018
        return (v * 2.0 ** -20).astype(jnp.bfloat16)
    # uniform with std about 1/sqrt(fan_in), to the nearest power of two
    e = round(math.log2(math.sqrt(3.0 / shape[0])))
    return (v * 2.0 ** (e - 15)).astype(jnp.bfloat16)


def _set(tree: dict, path, x) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = x


def program_params(seed: int, model: dict):
    """The served model's weights, bfloat16, layers stacked, built on the
    device in one jitted call."""
    L = model["n_layers"]

    def build(key):
        tree: dict = {}
        for path, shape, kind in _leaves(model):
            if path[0] == "layers":
                x = jax.vmap(lambda l, p=path, s=shape, k=kind:
                             _value(key, p, s, k, l))(jnp.arange(L))
            else:
                x = _value(key, path, shape, kind, 0)
            _set(tree, path, x)
        return tree

    return jax.jit(build)(base_key(seed))


@partial(jax.jit, static_argnames=("model_items", "glob"))
def _layer_params(key, layer, *, model_items, glob):
    model = dict(model_items)
    out: dict = {}
    for path, shape, kind in _leaves(model):
        if (path[0] == "layers") != (not glob):
            continue
        x = _value(key, path, shape, kind, layer if not glob else 0)
        _set(out, path[1:] if not glob else path, x.astype(jnp.float32))
    return out


def layer_params(seed: int, model: dict, layer: int | None) -> dict:
    """One layer's weights (``layer`` an int) or the global ones (None),
    float32, the same values as ``program_params`` holds."""
    return _layer_params(base_key(seed), jnp.int32(layer or 0),
                         model_items=tuple(sorted(model.items())),
                         glob=layer is None)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


LOWER = ("int8", "fp8")  # the precisions one step below bfloat16


def _fake_quant(x, axis, lowp):
    """Symmetric rounding along ``axis``, the absmax scaled to the
    format's largest value: int8 (127) or float8 e4m3 (448)."""
    top = 127.0 if lowp == "int8" else 448.0
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    s = jnp.where(s == 0, 1.0, s)
    if lowp == "int8":
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, lowp):
    """x [..., d_in] @ w [d_in, d_out]; with ``lowp`` (one of ``LOWER``)
    both operands are rounded to it first: activations per row, weights
    per output column."""
    if lowp:
        x, w = _fake_quant(x, -1, lowp), _fake_quant(w, 0, lowp)
    return jnp.dot(x, w)


def _rms(x, w, eps, rd=lambda x: x):
    return rd(rd(x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps))
              * w)


def _bf16(x):
    """x rounded to bfloat16, kept in float32."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _round_fn(bf16: bool):
    return _bf16 if bf16 else (lambda x: x)


def _rope(x, theta):
    """x [H, S, hd] at positions 0..S-1; rotates the two halves."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def _kv_round(x, rel):
    """PackKV's lossy step: each token's channels on a uniform grid of
    ``round(1 / rel) + 1`` levels spanning that token's min..max."""
    lo = jnp.min(x, -1, keepdims=True)
    rng = jnp.max(x, -1, keepdims=True) - lo
    step = jnp.where(rng > 0, rel * rng, 1.0)
    q = jnp.clip(jnp.round((x - lo) / step), 0, round(1.0 / rel))
    return q * step + lo


@partial(jax.jit, static_argnames=("model_items", "lowp", "kv_rel", "bf16"))
def _block(h, p, n_comp, *, model_items, lowp, kv_rel, bf16):
    """One layer over one padded sequence h [S, D] (causal, so padding at
    the end changes no earlier position). With ``kv_rel`` = (k_rel, v_rel)
    the query at position i reads keys and values j < n_comp[i] through
    PackKV's rounding, as the served cache holds them then."""
    m = dict(model_items)
    H, Hkv, hd, eps = m["n_heads"], m["n_kv_heads"], m["head_dim"], m["norm_eps"]
    S = h.shape[0]
    rd = _round_fn(bf16)
    mm = lambda x, w: rd(_mm(x, w, lowp))
    with jax.default_matmul_precision("highest"):
        x = _rms(h, p["ln1"], eps, rd)
        a = p["attn"]
        q = mm(x, a["wq"]).reshape(S, H, hd).transpose(1, 0, 2)
        k = mm(x, a["wk"]).reshape(S, Hkv, hd).transpose(1, 0, 2)
        v = mm(x, a["wv"]).reshape(S, Hkv, hd).transpose(1, 0, 2)
        if m["qk_norm"]:
            q, k = _rms(q, a["q_norm"], eps, rd), _rms(k, a["k_norm"], eps, rd)
        q, k = rd(_rope(q, m["rope_theta"])), rd(_rope(k, m["rope_theta"]))
        g = H // Hkv
        if kv_rel is not None:
            kq, vq = _kv_round(k, kv_rel[0]), _kv_round(v, kv_rel[1])
            kq, vq = jnp.repeat(kq, g, axis=0), jnp.repeat(vq, g, axis=0)
        k, v = jnp.repeat(k, g, axis=0), jnp.repeat(v, g, axis=0)

        def rows(i):
            qi = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 1)
            pos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
            s = jnp.einsum("hqd,hkd->hqk", qi, k) / math.sqrt(hd)
            if kv_rel is not None:
                nc = jax.lax.dynamic_slice_in_dim(n_comp, i * Q_BLOCK, Q_BLOCK)
                rd = jnp.arange(S)[None, :] < nc[:, None]  # [QB, S]
                sq = jnp.einsum("hqd,hkd->hqk", qi, kq) / math.sqrt(hd)
                s = jnp.where(rd, sq, s)
            s = jnp.where(pos[:, None] >= jnp.arange(S)[None, :], s, -jnp.inf)
            w = jax.nn.softmax(s, -1)
            if kv_rel is None:
                return jnp.einsum("hqk,hkd->hqd", w, v)
            return (jnp.einsum("hqk,hkd->hqd", jnp.where(rd, w, 0), vq)
                    + jnp.einsum("hqk,hkd->hqd", jnp.where(rd, 0, w), v))

        o = jax.lax.map(rows, jnp.arange(S // Q_BLOCK))  # [nb, H, QB, hd]
        o = rd(o.transpose(0, 2, 1, 3).reshape(S, H * hd))
        h = rd(h + mm(o, a["wo"]))
        x = _rms(h, p["ln2"], eps, rd)
        mp = p["mlp"]
        u = rd(rd(jax.nn.silu(mm(x, mp["w_gate"]))) * mm(x, mp["w_up"]))
        return rd(h + mm(u, mp["w_down"]))


def packkv_n_comp(prompt_len: int, n: int, kv: dict) -> np.ndarray:
    """Compressed tokens the served cache holds when the query at each of
    positions 0..n-1 attends. Prefill attends the raw prompt; from then on
    PackKV keeps every whole ``block`` of the prompt compressed and, once
    more than ``residual`` tokens wait in the full-precision residual,
    flushes whole blocks, up to ``capacity``."""
    out = np.zeros(n, np.int32)
    B, R = kv["block"], kv["residual"]
    lb = prompt_len // B * B
    for i in range(prompt_len, n):
        r = i + 1 - lb
        f = -(-(r - R) // B) if r > R else 0
        out[i] = lb + min(f, (kv["capacity"] - lb) // B) * B
    return out


@partial(jax.jit, static_argnames=("eps", "lowp", "bf16"))
def _logits(h, pos, g, *, eps, lowp, bf16):
    """Output-head logits [n, V] at rows ``pos`` of h [S, D]."""
    rd = _round_fn(bf16)
    with jax.default_matmul_precision("highest"):
        x = _rms(h[pos], g["final_ln"], eps, rd)
        return rd(_mm(x, g["head"], lowp))


@jax.jit
def _gaps(logits, tok):
    """best logit minus the logit of ``tok``, per row (>= 0)."""
    got = jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0]
    return jnp.max(logits, -1) - got


def served_gaps(seed: int, model: dict, seqs, pad_to: int, *,
                kv: dict | None = None, lowp: tuple = (),
                bf16: bool = False) -> list[dict]:
    """Run the reference over each ``(prompt, served)`` pair, at bfloat16
    precision with ``bf16`` and in float32 without.

    ``kv`` (``k_rel_scale``, ``v_rel_scale``, ``block``, ``residual``,
    ``capacity``) makes decode positions read the cache through PackKV's
    rounding at the precision the configuration states; None reads it
    exact. Returns one dict per sequence: ``served`` — at each served
    position, how far the served token's reference logit lies below the
    reference's best; ``first`` — the reference's logits [V] at the last
    prompt position, the ones that pick the first served token. For each
    precision ``m`` in ``lowp`` (of ``LOWER``: weights and activations of
    every matmul rounded to it), also ``m`` — the gap of the token that
    this lower-precision reference puts first at each served position —
    and ``m + "_first"``, its logits at the last prompt position. Runs
    layer by layer, one sequence at a time, so it fits beside nothing:
    call it once the program is freed.
    """
    pad_to = -(-pad_to // Q_BLOCK) * Q_BLOCK
    items = tuple(sorted(model.items()))
    rel = None if kv is None else (kv["k_rel_scale"], kv["v_rel_scale"])
    embed = layer_params(seed, model, None)["embed"]
    hs, ncomp = {}, []
    for i, (prompt, served) in enumerate(seqs):
        ids = np.zeros(pad_to, np.int32)
        full = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        ids[: len(full)] = full
        ncomp.append(jnp.asarray(packkv_n_comp(len(prompt), pad_to, kv) if kv
                                 else np.zeros(pad_to, np.int32)))
        for mode in (None, *lowp):
            hs[mode, i] = embed[jnp.asarray(ids)]
    del embed
    for layer in range(model["n_layers"]):
        p = layer_params(seed, model, layer)
        for key in hs:
            hs[key] = _block(hs[key], p, ncomp[key[1]], model_items=items,
                             lowp=key[0], kv_rel=rel, bf16=bf16)
        del p
    g = layer_params(seed, model, None)
    out = []
    for i, (prompt, served) in enumerate(seqs):
        pos = jnp.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
        ref = _logits(hs[None, i], pos, g, eps=model["norm_eps"], lowp=None,
                      bf16=bf16)
        res = {"served": np.asarray(_gaps(ref, jnp.asarray(served, jnp.int32))),
               "first": np.asarray(ref[0])}
        for mode in lowp:
            low = _logits(hs[mode, i], pos, g, eps=model["norm_eps"],
                          lowp=mode, bf16=bf16)
            res[mode] = np.asarray(
                _gaps(ref, jnp.argmax(low, -1).astype(jnp.int32)))
            res[mode + "_first"] = np.asarray(low[0])
        out.append(res)
    return out
