"""GPT-2 decoder (Radford et al. 2019, "Language Models are Unsupervised
Multitask Learners"; configuration openai-community/gpt2-large): pre-norm
blocks with LayerNorm (eps 1e-5), multi-head attention with QKV bias, a
GELU (tanh approximation) MLP of width 4d, a final LayerNorm, and logits
from the tied token embedding.

Departures from the published model, as the configuration under test
runs it (``bench/configs/gpt2-large.json``): rotary position embeddings
(theta 10000) on queries and keys instead of learned absolute position
embeddings; no bias on the attention output projection or on the two
MLP projections.
"""
import jax.numpy as jnp

from reference.common import attention, layer_norm, mm

EPS = 1e-5


def layer_shapes(cfg):
    d, H, ff = cfg["d_model"], cfg["num_heads"], cfg["d_ff"]
    hd = d // H
    return {"ln1/gamma": (d,), "ln1/beta": (d,),
            "attn/wq": (d, H, hd), "attn/wk": (d, H, hd),
            "attn/wv": (d, H, hd), "attn/wo": (H, hd, d),
            "attn/bq": (H, hd), "attn/bk": (H, hd), "attn/bv": (H, hd),
            "ln2/gamma": (d,), "ln2/beta": (d,),
            "ffn/w_up": (d, ff), "ffn/w_down": (ff, d)}


def top_shapes(cfg):
    d = cfg["d_model"]
    return {"embedding": (cfg["vocab_size"], d),
            "final_norm/gamma": (d,), "final_norm/beta": (d,)}


def gelu(x):
    return 0.5 * x * (1 + jnp.tanh(jnp.sqrt(2 / jnp.pi)
                                   * (x + 0.044715 * x ** 3)))


def block(cfg, w, x, mode):
    h = layer_norm(x, w["ln1/gamma"], w["ln1/beta"], EPS)
    x = x + attention(cfg, w, h, mode)
    h = layer_norm(x, w["ln2/gamma"], w["ln2/beta"], EPS)
    return x + mm("btf,fd->btd", gelu(mm("btd,df->btf", h, w["ffn/w_up"],
                                         mode)), w["ffn/w_down"], mode)


def final(cfg, top, h):
    return layer_norm(h, top["final_norm/gamma"], top["final_norm/beta"],
                      EPS)


def logits(cfg, top, h, mode):
    return mm("btd,vd->btv", h, top["embedding"], mode)
