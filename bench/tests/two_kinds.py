"""A toy reference family with two kinds of layer, for the tests of the
layer-at-a-time driver: ``attn`` layers (pre-RMSNorm causal attention
with QKV bias) and ``mlp`` layers (pre-RMSNorm SwiGLU), whose tensors
differ in name and shape, in the order the configuration's ``pattern``
gives."""
import jax
import jax.numpy as jnp

from reference.common import attention, mm, rms_norm

EPS = 1e-6


def kinds(cfg):
    return list(cfg["pattern"])


def layer_shapes(cfg, kind):
    d, H, Hkv, ff = (cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"],
                     cfg["d_ff"])
    hd = d // H
    if kind == "attn":
        return {"ln1/gamma": (d,),
                "attn/wq": (d, H, hd), "attn/wk": (d, Hkv, hd),
                "attn/wv": (d, Hkv, hd), "attn/wo": (H, hd, d),
                "attn/bq": (H, hd), "attn/bk": (Hkv, hd),
                "attn/bv": (Hkv, hd)}
    return {"ln2/gamma": (d,), "ffn/w_up": (d, ff), "ffn/w_gate": (d, ff),
            "ffn/w_down": (ff, d)}


def top_shapes(cfg):
    d, V = cfg["d_model"], cfg["vocab_size"]
    return {"embedding": (V, d), "unembed": (d, V), "final_norm/gamma": (d,)}


def block(cfg, w, x, mode, kind):
    if kind == "attn":
        return x + attention(cfg, w, rms_norm(x, w["ln1/gamma"], EPS), mode)
    h = rms_norm(x, w["ln2/gamma"], EPS)
    act = (jax.nn.silu(mm("btd,df->btf", h, w["ffn/w_gate"], mode))
           * mm("btd,df->btf", h, w["ffn/w_up"], mode))
    return x + mm("btf,fd->btd", act, w["ffn/w_down"], mode)


def final(cfg, top, h):
    return rms_norm(h, top["final_norm/gamma"], EPS)


def logits(cfg, top, h, mode):
    return mm("btd,dv->btv", h, top["unembed"], mode)
