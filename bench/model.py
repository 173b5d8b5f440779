"""A configuration file (``bench/configs/<name>.json``) and the program
configuration it runs as.

The file holds the published config's keys, with every key changed
from the source listed in ``reduced``.  ``registry`` names the
program's own configuration and ``overrides`` the fields the benchmark
changes in it, each with its reason.  ``program_config`` applies them
and then checks every size the program will run against the file, so
the file is the configuration as it is run.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

NORMS = {"layernorm": "ln", "rmsnorm": "rms"}


@dataclasses.dataclass(frozen=True)
class Arch:
    name: str
    registry: str
    d_model: int
    d_ff: int
    n_layers: int
    n_heads: int
    n_kv: int
    head_dim: int
    vocab: int
    rotary_pct: float
    rope_theta: float
    norm: str              # layernorm | rmsnorm
    norm_eps: float
    qkv_bias: bool
    tie_embeddings: bool
    overrides: tuple       # ((program field, value), ...)

    @classmethod
    def load(cls, path: Path) -> "Arch":
        d = json.loads(Path(path).read_text())
        if d.get("hidden_act") != "silu":
            raise ValueError(f"{path}: only SwiGLU (silu) MLPs are "
                             f"modelled, got {d.get('hidden_act')!r}")
        H = int(d["num_attention_heads"])
        norm = d["norm"]
        eps = d["layer_norm_eps"] if norm == "layernorm" \
            else d["rms_norm_eps"]
        return cls(
            name=d["name"], registry=d["registry"],
            d_model=int(d["hidden_size"]),
            d_ff=int(d["intermediate_size"]),
            n_layers=int(d["num_hidden_layers"]), n_heads=H,
            n_kv=int(d["num_key_value_heads"]),
            head_dim=int(d["hidden_size"]) // H,
            vocab=int(d["vocab_size"]),
            rotary_pct=float(d.get("partial_rotary_factor", 1.0)),
            rope_theta=float(d["rope_theta"]), norm=norm,
            norm_eps=float(eps),
            qkv_bias=bool(d.get("use_qkv_bias", d.get("qkv_bias", False))),
            tie_embeddings=bool(d["tie_word_embeddings"]),
            overrides=tuple((k, v["value"])
                            for k, v in d.get("overrides", {}).items()))

    @property
    def rotary_dim(self) -> int:
        rd = int(self.head_dim * self.rotary_pct)
        return rd - rd % 2

    def program_config(self):
        """The program's ModelConfig for this file, checked against it."""
        from repro.configs import get_config
        cfg = dataclasses.replace(get_config(self.registry),
                                  **dict(self.overrides))
        want = {"d_model": self.d_model, "d_ff": self.d_ff,
                "n_layers": self.n_layers, "n_heads": self.n_heads,
                "n_kv": self.n_kv, "head_dim": self.head_dim,
                "vocab": self.vocab, "rotary_pct": self.rotary_pct,
                "rope_theta": self.rope_theta,
                "norm": NORMS[self.norm], "qkv_bias": self.qkv_bias,
                "tie_embeddings": self.tie_embeddings, "act": "silu",
                "pos_emb": "rope", "kv_cache_dtype": "bf16"}
        bad = {k: (getattr(cfg, k), v) for k, v in want.items()
               if getattr(cfg, k) != v}
        if bad:
            raise ValueError(f"{self.name}: the program's configuration "
                             f"differs from the file (program, file): "
                             f"{bad}")
        if any(s.kind != "attn" or s.moe for s in cfg.pattern):
            raise ValueError(f"{self.name}: only dense attention layers "
                             f"are modelled")
        return cfg

    def matmul_params(self) -> int:
        """Parameters that every token multiplies through (the LM head
        included, the embedding lookup not)."""
        D, H, KV, hd, F = (self.d_model, self.n_heads, self.n_kv,
                           self.head_dim, self.d_ff)
        layer = D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F
        return self.n_layers * layer + self.vocab * D
