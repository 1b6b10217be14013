"""int8 (W8A8) quantized serving path for the trace transformer.

The MXU runs s8 x s8 -> s32 at twice the bf16 rate on v5e (pallas guide:
int8 tile (32, 128); "Patterns: Quantization Kernels"). Serving is
throughput-bound on the FFN/QKV matmuls (~92% of FLOPs), so the quantized
scorer runs exactly those in int8 with:

* per-output-channel symmetric weight scales, quantized ONCE at load
  (weights are device-resident int8 — also halves HBM traffic), and
* per-token dynamic activation scales (absmax / 127), computed on the VPU.

Attention score/value matmuls, layernorms, embeddings, and the fp32 heads
stay in bf16/fp32 — they are a few percent of the FLOPs and carry most of
the numerical sensitivity. The forward mirrors models.layers/transformer
parameter-for-parameter, so any trained checkpoint serves quantized with
no re-export. Accuracy is asserted against the float path in tests.

Not the default: the per-token quantize/dequantize is elementwise work
on the VPU over every activation, and at the flagship's matmul sizes it
can cost more than the halved MXU time saves. How the two compare on the
chip, and at which width int8 starts to win, is not measured on this
tree (ROADMAP S5 measures it; D4 decides from the result). The path
stays OPT-IN (``EngineConfig.quantized`` / processor config
``quantized: true``). Score parity and AUC against the float path are
asserted in tests (tests/test_northstar_auc.py holds int8 to the same
>=0.95 bar).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..features.featurizer import CAT_FIELDS
from . import jitstats

# see models/transformer.py: every jitted scoring entry point declares its
# recompile-bounding strategy (asserted by the package hygiene test)
SHAPE_BUCKETING = {
    "score_packed": "packed row axis padded by BucketLadder.round_rows "
                    "(serving.engine); L/C fixed by the wrapped model's "
                    "TransformerConfig",
}


def quantize_weight(w: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-output-channel symmetric int8: w (in, out) -> (w_q int8, scale
    (out,) f32). Zero columns get scale 1 to avoid div-by-zero."""
    w = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(w), axis=0)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    w_q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return w_q, scale


def _qdense(x: jnp.ndarray, w_q: jnp.ndarray, w_s: jnp.ndarray,
            b: jnp.ndarray | None, out_dtype) -> jnp.ndarray:
    """y = dequant(quant(x) @ w_q) + b with per-token activation scales.
    x: (..., in); w_q: (in, out) int8."""
    xf = x.astype(jnp.float32)
    a_max = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    a_s = jnp.where(a_max > 0, a_max / 127.0, 1.0)
    x_q = jnp.clip(jnp.round(xf / a_s), -127, 127).astype(jnp.int8)
    # s8 x s8 -> s32 rides the MXU at 2x the bf16 rate
    acc = jax.lax.dot_general(
        x_q, w_q, (((x_q.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    y = acc.astype(jnp.float32) * (a_s * w_s)
    if b is not None:
        y = y + b.astype(jnp.float32)
    return y.astype(out_dtype)


def _layernorm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
               dtype) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + 1e-6)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dtype)


class QuantizedTraceScorer:
    """Serves a trained TraceTransformer with int8 matmuls.

    >>> scorer = QuantizedTraceScorer(model, variables)
    >>> probs = scorer.score_packed(cat, cont, segments, positions)
    """

    def __init__(self, model, variables):
        self.cfg = model.cfg
        self.params = self._prepare(variables["params"])
        # (R, L) span anomaly probabilities — drop-in for
        # TraceTransformer.score_packed, minus the variables argument
        # (the quantized weights are closed over)
        score_packed = jax.jit(self._score_packed_impl)
        self.score_packed = jitstats.track_jit("quantized.score_packed",
                                               score_packed)

    # ------------------------------------------------------------- prepare

    def _prepare(self, p) -> dict[str, Any]:
        """Quantize the throughput-bound kernels once; keep the rest as
        loaded. Shapes follow flax's module tree (layers.py)."""
        c = self.cfg
        enc = p["encoder"]
        out: dict[str, Any] = {
            "embed": enc["embed"],
            "pos": enc["pos_embed"]["embedding"],
            "final_ln": enc["final_ln"],
            "span_head": p["span_head"],
            "trace_head": p["trace_head"],
            "blocks": [],
        }
        for i in range(c.n_layers):
            blk = enc[f"block_{i}"]
            mha = blk["MultiHeadDotProductAttention_0"]
            d = c.d_model

            def qkv(leaf):  # (d, heads, head_dim) -> quantized (d, d)
                w_q, w_s = quantize_weight(
                    leaf["kernel"].reshape(d, -1))
                return {"w": w_q, "s": w_s,
                        "b": leaf["bias"].reshape(-1)}

            w_q, w_s = quantize_weight(
                mha["out"]["kernel"].reshape(-1, d))
            out["blocks"].append({
                "ln1": blk["LayerNorm_0"],
                "q": qkv(mha["query"]),
                "k": qkv(mha["key"]),
                "v": qkv(mha["value"]),
                "o": {"w": w_q, "s": w_s, "b": mha["out"]["bias"]},
                "ln2": blk["LayerNorm_1"],
                "ffn1": dict(zip(("w", "s"), quantize_weight(
                    blk["Dense_0"]["kernel"])),
                    b=blk["Dense_0"]["bias"]),
                "ffn2": dict(zip(("w", "s"), quantize_weight(
                    blk["Dense_1"]["kernel"])),
                    b=blk["Dense_1"]["bias"]),
            })
        return jax.device_put(out)

    # ------------------------------------------------------------- forward

    def _embed(self, cat, cont):
        c, e = self.cfg, self.params["embed"]
        dt = c.dtype
        svc = e["service_embed"]["embedding"].astype(dt)
        x = svc[cat[..., 0]]
        x += e["name_embed"]["embedding"].astype(dt)[cat[..., 1]]
        x += e["kind_embed"]["embedding"].astype(dt)[cat[..., 2]]
        x += e["status_embed"]["embedding"].astype(dt)[cat[..., 3]]
        x += svc[cat[..., 4]]
        n_attr = cat.shape[-1] - len(CAT_FIELDS)
        if n_attr > 0:
            attr = e["attr_embed"]["embedding"].astype(dt)
            x += attr[cat[..., len(CAT_FIELDS):]].sum(axis=-2)
        cp = e["cont_proj"]
        x += (cont.astype(dt) @ cp["kernel"].astype(dt)
              + cp["bias"].astype(dt))
        return x

    def _block(self, blk, x, attn_mask):
        c = self.cfg
        dt = c.dtype
        H, hd = c.n_heads, c.d_model // c.n_heads
        # the float path's part names (layers.PARTS), so that a trace of
        # either scorer folds the same way
        with jax.named_scope("attn"):
            h = _layernorm(x, blk["ln1"]["scale"], blk["ln1"]["bias"], dt)
            R, L, _ = h.shape

            def heads(proj):
                y = _qdense(h, proj["w"], proj["s"], proj["b"], dt)
                return y.reshape(R, L, H, hd)

            q, k, v = heads(blk["q"]), heads(blk["k"]), heads(blk["v"])
            # attention internals stay bf16 (few % of FLOPs, most
            # sensitivity)
            scores = jnp.einsum("rlhd,rmhd->rhlm", q, k) / jnp.sqrt(
                jnp.asarray(hd, jnp.float32)).astype(dt)
            scores = jnp.where(attn_mask, scores.astype(jnp.float32),
                               -1e9)
            attn = jax.nn.softmax(scores, axis=-1).astype(dt)
            ctx = jnp.einsum("rhlm,rmhd->rlhd", attn, v).reshape(R, L, -1)
            x = x + _qdense(ctx, blk["o"]["w"], blk["o"]["s"],
                            blk["o"]["b"], dt)
        with jax.named_scope("mlp"):
            h = _layernorm(x, blk["ln2"]["scale"], blk["ln2"]["bias"], dt)
            h = _qdense(h, blk["ffn1"]["w"], blk["ffn1"]["s"],
                        blk["ffn1"]["b"], dt)
            h = jax.nn.gelu(h)
            return x + _qdense(h, blk["ffn2"]["w"], blk["ffn2"]["s"],
                               blk["ffn2"]["b"], dt)

    def _score_packed_impl(self, cat, cont, segments, positions):
        c, p = self.cfg, self.params
        dt = c.dtype
        mask = segments > 0
        with jax.named_scope("embed"):
            x = self._embed(cat, cont)
            x = x + p["pos"].astype(dt)[positions]
            x = x * mask[..., None].astype(dt)
        with jax.named_scope("attn_mask"):
            attn_mask = ((segments[..., None] == segments[..., None, :])
                         & mask[..., None] & mask[..., None, :])[:, None]
        for blk in p["blocks"]:
            x = self._block(blk, x, attn_mask)
        with jax.named_scope("final_norm"):
            x = _layernorm(x, p["final_ln"]["scale"],
                           p["final_ln"]["bias"], dt)
        with jax.named_scope("head"):
            head = p["span_head"]
            logit = (x.astype(jnp.float32)
                     @ head["kernel"].astype(jnp.float32)
                     + head["bias"].astype(jnp.float32))[..., 0]
            return jax.nn.sigmoid(logit)
