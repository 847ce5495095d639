"""A configuration file's sizes, as the benchmark reads them.

``bench/configs/<config>.json`` holds the published ``config.json`` keys
of a model (Hugging Face names) plus three groups of the benchmark's own:
``model`` (the block's mechanisms), ``serving`` (how the cell serves it:
weight bits, dtypes, resident share, slots, ``max_len``) and ``correct``
(the comparison's sample size and limit).
"""

from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class Dims:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm: str              # rmsnorm | nonparam_ln
    norm_eps: float
    qk_norm: bool
    rope_theta: float
    tied: bool
    weight_bits: int
    slots: int
    max_len: int

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim


NORM_EPS = {"rmsnorm": "rms_norm_eps"}


def dims(name: str, conf: Dict) -> Dims:
    m, s = conf["model"], conf["serving"]
    if m["family"] != "dense" or m["mlp"] != "swiglu":
        raise ValueError(f"{name}: the harness serves dense swiglu decoders")
    heads = conf["num_attention_heads"]
    d = conf["hidden_size"]
    norm = m["norm"]
    # non-parametric LayerNorm (OLMo) uses the LayerNorm default 1e-5
    eps = conf[NORM_EPS[norm]] if norm in NORM_EPS else 1e-5
    return Dims(
        name=name, n_layers=conf["num_hidden_layers"], d_model=d,
        n_heads=heads,
        n_kv_heads=conf.get("num_key_value_heads", heads),
        head_dim=conf.get("head_dim", d // heads),
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        norm=norm, norm_eps=float(eps), qk_norm=bool(m["qk_norm"]),
        rope_theta=float(conf["rope_theta"]),
        tied=bool(conf["tie_word_embeddings"]),
        weight_bits=int(s["weight_bits"]), slots=int(s["slots"]),
        max_len=int(s["max_len"]))


# (name, rows, cols) of each packed matrix of a layer, as the program
# stores it: y = x @ W^T with W (rows, cols)
def layer_matrices(d: Dims):
    return (("attn", "wq", d.q_dim, d.d_model),
            ("attn", "wk", d.kv_dim, d.d_model),
            ("attn", "wv", d.kv_dim, d.d_model),
            ("attn", "wo", d.d_model, d.q_dim),
            ("mlp", "w_gate", d.d_ff, d.d_model),
            ("mlp", "w_up", d.d_ff, d.d_model),
            ("mlp", "w_down", d.d_model, d.d_ff))
