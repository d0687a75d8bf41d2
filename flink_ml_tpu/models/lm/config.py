"""The decoder LM's sizes and its parameter tree, shared by the stage
(``decoder_lm.py``) and the plain reference (``reference.py``) so that one set
of weights can be handed to both.

The tree: ``{"embed": [V, d], "layers": [layer, ...], "final_norm": [d],
"lm_head": [d, V]}`` with ``layer = {"attn_norm": [d], "wq"/"wk"/"wv"/"wo":
[d, d], "q_norm"/"k_norm": [d], "ffn_norm": [d], "router": [d, E],
"w_gate"/"w_up": [E, d, h], "w_down": [E, h, d]}``. A matrix maps ``x @ W``
(``[in, out]``: the transpose of a ``torch.nn.Linear`` weight).
"""
from __future__ import annotations

from typing import List, NamedTuple, Tuple

__all__ = ["LMConfig", "param_shapes", "num_params"]


class LMConfig(NamedTuple):
    n_layers: int
    hidden: int
    n_heads: int
    n_experts: int
    top_k: int
    expert_width: int
    vocab: int
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    aux_coef: float = 0.01

    @property
    def head_dim(self) -> int:
        return self.hidden // self.n_heads


_LAYER_NORMS = ("attn_norm", "q_norm", "k_norm", "ffn_norm")


def param_shapes(cfg: LMConfig) -> List[Tuple[tuple, tuple, bool]]:
    """Every leaf as ``(path, shape, is_norm)``, in the one order the
    initialiser numbers them by. ``path`` indexes the tree: ``("layers", 0,
    "wq")``."""
    d, e, h = cfg.hidden, cfg.n_experts, cfg.expert_width
    out = [(("embed",), (cfg.vocab, d), False)]
    for i in range(cfg.n_layers):
        for name, shape in (
            ("attn_norm", (d,)), ("wq", (d, d)), ("wk", (d, d)), ("wv", (d, d)), ("wo", (d, d)),
            ("q_norm", (d,)), ("k_norm", (d,)), ("ffn_norm", (d,)), ("router", (d, e)),
            ("w_gate", (e, d, h)), ("w_up", (e, d, h)), ("w_down", (e, h, d)),
        ):
            out.append((("layers", i, name), shape, name in _LAYER_NORMS))
    out.append((("final_norm",), (d,), True))
    out.append((("lm_head",), (d, cfg.vocab), False))
    return out


def num_params(cfg: LMConfig) -> int:
    total = 0
    for _, shape, _ in param_shapes(cfg):
        n = 1
        for s in shape:
            n *= s
        total += n
    return total
