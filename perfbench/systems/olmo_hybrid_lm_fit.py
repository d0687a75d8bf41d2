"""System builder ``olmo_hybrid_lm_fit``: ``DecoderLM`` with ``blockKind``
``olmo_hybrid`` through ``Estimator.fit`` on packed token sequences made from
the seed: one chip's share of a tensor- and vocabulary-parallel Olmo-Hybrid-7B
job (the HEADS held here of every mixer and the slice of the untied embedding
and head held here; the dense SwiGLU, ``o_norm`` and the output norms whole),
the first period of the published layer pattern.

The benchmark makes the inputs (``DecoderLmFit.make_data``, over the
vocabulary slice) and holds the plain reference's inputs; everything between
``fit()`` and the losses and gradient norms it reports is the program's.
``layer_types`` stays as published, 32 entries: those under
``num_hidden_layers`` run.
"""
from __future__ import annotations

import gc
import json

from perfbench.references import olmo_hybrid_lm as reference
from perfbench.systems.decoder_lm_fit import DecoderLmFit, import_program  # noqa: F401 - the harness calls it

#: The configuration's keys the reference's equations read, as the file has them.
DIMS = ("num_hidden_layers", "layer_types", "hidden_size", "intermediate_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
        "linear_value_head_dim", "linear_conv_kernel_dim", "vocab_size", "rms_norm_eps", "time_step_min",
        "time_step_max", "time_step_floor")
#: The delta rule's own leaves: what reads the precision its decays and state were carried in.
RULE_LEAVES = ("A_log", "dt_bias", "Wa", "Wb")
#: The feed-forwards' matrices and the head: the step's largest gradients.
FFN_LEAVES = ("w_gate", "w_up", "w_down", "lm_head")


def _dims(config: dict) -> dict:
    dims = {k: config[k] for k in DIMS}
    for key, want in (("tie_word_embeddings", False), ("attention_bias", False), ("hidden_act", "silu"),
                      ("linear_allow_neg_eigval", True), ("rope_parameters", {"rope_theta": None})):
        if config[key] != want:
            raise ValueError(f"the olmo_hybrid block is written for {key} = {want}, the configuration has "
                             f"{config[key]}")
    if dims["linear_num_key_heads"] != dims["linear_num_value_heads"]:
        raise ValueError("the delta rule here takes as many value heads as key heads")
    if dims["num_attention_heads"] % dims["num_key_value_heads"]:
        raise ValueError("the held query heads divide evenly over the held key/value heads")
    dims["layer_types"] = tuple(dims["layer_types"])
    return dims


def lm_config(config: dict):
    """The program's ``LMConfig`` of this configuration (the tests size the
    parameter tree from it without a fit)."""
    from flink_ml_tpu.models.lm.config import LMConfig

    d = _dims(config)
    return LMConfig(
        d["num_hidden_layers"], d["hidden_size"], d["num_attention_heads"], 0, 0, d["intermediate_size"],
        d["vocab_size"], norm_eps=float(d["rms_norm_eps"]), aux_coef=0.0, block="olmo_hybrid",
        n_kv_heads=d["num_key_value_heads"], head_size=d["head_dim"], conv_kernel=d["linear_conv_kernel_dim"],
        chunk=config["chunk_size"], gqa_layers=tuple(reference.attending(d)), kda_heads=d["linear_num_key_heads"],
        kda_head_dim=d["linear_key_head_dim"], kda_value_dim=d["linear_value_head_dim"])


class OlmoHybridLmFit(DecoderLmFit):
    """``DecoderLmFit``'s data (``make_tokens`` over the slice's ids), DataFrame
    and job size; this configuration's sizes, estimator, reference and check."""

    def __init__(self, config: dict, seed: int, n_devices: int):
        self.cfg = config
        self.seed = seed
        self.n_devices = n_devices
        self.n_seq = int(config["num_sequences"])
        self.seq_len = int(config["sequence_length"])
        self.batch = int(config["global_batch_size"])
        self.steps = int(config["max_iter"])
        self.dims = _dims(config)
        self.hyper = {k: float(config[k]) for k in ("learning_rate", "weight_decay", "clip_norm", "init_std")}
        self.tok = self.df = None
        d = self.dims
        # the shapes perfbench/olmo_hybrid_costs.py takes
        self.layout_dims = {
            "tokens": self.batch * self.seq_len, "batch": self.batch, "seq": self.seq_len,
            "hidden": d["hidden_size"], "layers": d["num_hidden_layers"], "layers_full": len(reference.attending(d)),
            "kda_heads": d["linear_num_key_heads"], "key_dim": d["linear_key_head_dim"],
            "value_dim": d["linear_value_head_dim"], "conv_kernel": d["linear_conv_kernel_dim"],
            "heads": d["num_attention_heads"], "kv_heads": d["num_key_value_heads"], "head_dim": d["head_dim"],
            "width": d["intermediate_size"], "vocab": d["vocab_size"],
        }

    # -- the job ----------------------------------------------------------------
    def fit(self) -> dict:
        """One whole fit job; returns host-side numbers only, so that nothing
        pins the fit's device arrays while the next fit allocates its own."""
        from flink_ml_tpu.models.lm import DecoderLM

        d = self.dims
        est = (
            DecoderLM().set_block_kind("olmo_hybrid")  # first: a program without the kind refuses here, by name
            .set_num_layers(d["num_hidden_layers"]).set_gqa_layers(reference.attending(d))
            .set_hidden_size(d["hidden_size"])
            .set_kda_num_heads(d["linear_num_key_heads"]).set_kda_head_size(d["linear_key_head_dim"])
            .set_kda_value_head_size(d["linear_value_head_dim"])
            .set_ssm_conv_kernel(d["linear_conv_kernel_dim"]).set_ssm_chunk_size(self.cfg["chunk_size"])
            .set_num_heads(d["num_attention_heads"]).set_num_kv_heads(d["num_key_value_heads"])
            .set_head_size(d["head_dim"]).set_expert_width(d["intermediate_size"])
            .set_vocab_size(d["vocab_size"]).set_norm_eps(float(d["rms_norm_eps"]))
            .set_compute_type(self.cfg["compute_dtype"])
            .set_max_iter(self.steps).set_global_batch_size(self.batch)
            .set_learning_rate(self.hyper["learning_rate"]).set_seed(self.seed % (2 ** 31))
        )
        model = est.fit(self.df)
        del model  # its parameters leave the device here
        return {
            "losses": list(est.loss_history),
            "grad_norms": list(est.grad_norm_history),
            "group_norms": dict(zip(est.param_names, est.param_grad_norm_history[0])),
            "steps_expected": self.steps,
        }

    # -- the output check ---------------------------------------------------------
    def reference(self, precision: str = "f32") -> dict:
        """The head of the job from the same seed, by the plain reference on
        this device: two steps' losses, the first step's gradient norms. A fit
        is a function of the seed alone, so the last completed fit's first two
        steps ARE the head of the job the reference computes."""
        gc.collect()
        lo2 = self.batch if 2 * self.batch <= self.n_seq else 0
        batches = [self.tok[: self.batch], self.tok[lo2: lo2 + self.batch]]
        out = reference.head_of_job(self.dims, self.hyper, self.seed % (2 ** 31), batches, precision)
        out.update(steps_expected=2)
        return out

    def compare(self, got: dict, want: dict) -> dict:
        """The numbers the check holds to their limits: program (or control)
        ``got`` against the float32 reference ``want``.

        ``kda_grad_norm_rel_err`` is the worst relative error of the gradient
        norm over the delta rule's own leaves (``A_log``, ``dt_bias``, ``Wa``,
        ``Wb`` of every such layer): their gradients sum over every position's
        decay, correction and state, so they read what precision the rule's
        decays and state were carried in; ``group_grad_norm_rel_err`` is the
        worst of every OTHER leaf. ``ffn_grad_norm_rel_err`` is the worst of
        the feed-forwards' matrices and the head (``w_gate``, ``w_up``,
        ``w_down`` of every layer, ``lm_head``): the step's largest gradients,
        each a contraction over all 8,192 positions, which matmul inputs
        rounded to bfloat16 under float32 accumulation leave within 7e-4 and
        accumulators' results in bfloat16 do not (3e-3 and more): with no
        router to flip, it is the number that tells the bfloat16 control."""
        def rel(a, b):
            return abs(a - b) / abs(b) if b else float(a != 0.0)

        whole = want["grad_norms"][0]
        group = {k: rel(got["group_norms"][k], v) for k, v in want["group_norms"].items()}
        leaf = lambda k: k.rsplit(".", 1)[-1]  # noqa: E731
        worst = {name: max(ks, key=group.get) for name, ks in (
            ("rule", [k for k in group if leaf(k) in RULE_LEAVES]),
            ("other", [k for k in group if leaf(k) not in RULE_LEAVES]),
            ("ffn", [k for k in group if leaf(k) in FFN_LEAVES]))}
        table = sorted(group, key=group.get, reverse=True)[:6]
        print("check_groups " + "; ".join(f"{k} {group[k]:.2e} at {want['group_norms'][k] / whole:.1e} of the whole"
                                          for k in table), flush=True)
        print("check_group_norms " + json.dumps({k: [float(got["group_norms"][k]), v]
                                                 for k, v in want["group_norms"].items()}), flush=True)
        print(f"check_detail losses {[round(x, 4) for x in got['losses']]} against {want['losses']}; "
              + "; ".join(f"worst {name} leaf {k} {group[k]:.3e}" for name, k in worst.items()), flush=True)
        loss = [rel(g, w) for g, w in zip(got["losses"], want["losses"])] + [float("inf")] * 2
        return {
            "loss_rel_err": loss[0],
            "loss_after_update_rel_err": loss[1],
            "grad_norm_rel_err": rel(got["grad_norms"][0], want["grad_norms"][0]),
            "group_grad_norm_rel_err": group[worst["other"]],
            "kda_grad_norm_rel_err": group[worst["rule"]],
            "ffn_grad_norm_rel_err": group[worst["ffn"]],
            "steps_missing": float(got["steps_expected"] - len(got["losses"])),
        }


def create(config: dict, seed: int, n_devices: int):
    return OlmoHybridLmFit(config, seed, n_devices)
