"""System builder ``solar_lm_fit``: ``DecoderLM`` with ``blockKind``
``solar_open2`` through ``Estimator.fit`` on packed token sequences made from
the seed: one chip's share of a tensor-, expert- and vocabulary-parallel
Solar-Open2-250B job (the HEADS held here of every mixer, the experts held
here, the slice of the untied embedding and head held here; the gates'
low-rank down-projections, the router, the shared expert and the norms whole),
the first period of the published layer pattern.

The benchmark makes the inputs (``DecoderLmFit.make_data``, over the
vocabulary slice) and holds the plain reference's inputs; everything between
``fit()`` and the losses, gradient norms and expert loads it reports is the
program's. ``gqa_layers`` stays as published, twelve indices: those under
``num_hidden_layers`` are the layers that attend.
"""
from __future__ import annotations

import gc
import json

import numpy as np

from perfbench.references import solar_lm as reference
from perfbench.systems.decoder_lm_fit import DecoderLmFit, import_program  # noqa: F401 - the harness calls it

#: The configuration's keys the reference's equations read, as the file has them.
DIMS = ("num_hidden_layers", "gqa_layers", "hidden_size", "linear_attn_config", "num_attention_heads",
        "num_key_value_heads", "head_dim", "n_routed_experts", "n_routed_experts_published", "first_expert_held",
        "num_experts_per_tok", "moe_intermediate_size", "n_shared_experts", "routed_scaling_factor", "vocab_size",
        "rms_norm_eps", "time_step_min", "time_step_max", "time_step_floor")


def _dims(config: dict) -> dict:
    dims = {k: config[k] for k in DIMS}
    for key, want in (("tie_word_embeddings", False), ("use_rope", False), ("use_gqa_gate", True),
                      ("kda_use_full_proj", False), ("kda_allow_neg_eigval", True), ("norm_topk_prob", True),
                      ("first_k_dense_replace", 0), ("n_shared_experts", 1)):
        if config[key] != want:
            raise ValueError(f"the solar_open2 block is written for {key} = {want}, the configuration has "
                             f"{config[key]}")
    if dims["num_attention_heads"] % dims["num_key_value_heads"]:
        raise ValueError("the held query heads divide evenly over the held key/value heads")
    return dims


def lm_config(config: dict):
    """The program's ``LMConfig`` of this configuration (the tests size the
    parameter tree from it without a fit)."""
    from flink_ml_tpu.models.lm.config import LMConfig

    d = _dims(config)
    delta = d["linear_attn_config"]
    return LMConfig(
        d["num_hidden_layers"], d["hidden_size"], d["num_attention_heads"], d["n_routed_experts_published"],
        d["num_experts_per_tok"], d["moe_intermediate_size"], d["vocab_size"], norm_eps=float(d["rms_norm_eps"]),
        aux_coef=0.0, block="solar_open2", experts_held=d["n_routed_experts"], first_held=d["first_expert_held"],
        n_kv_heads=d["num_key_value_heads"], head_size=d["head_dim"],
        shared_width=d["n_shared_experts"] * d["moe_intermediate_size"],
        routed_scale=float(d["routed_scaling_factor"]), conv_kernel=delta["short_conv_kernel_size"],
        chunk=config["chunk_size"], gqa_layers=tuple(reference.attending(d)), kda_heads=delta["num_heads"],
        kda_head_dim=delta["head_dim"])


class SolarLmFit(DecoderLmFit):
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
        d, delta = self.dims, self.dims["linear_attn_config"]
        attends = reference.attending(d)
        # the shapes perfbench/solar_costs.py takes
        self.layout_dims = {
            "tokens": self.batch * self.seq_len, "batch": self.batch, "seq": self.seq_len,
            "hidden": d["hidden_size"], "layers": d["num_hidden_layers"], "layers_gqa": len(attends),
            "kda_heads": delta["num_heads"], "kda_head_dim": delta["head_dim"],
            "conv_kernel": delta["short_conv_kernel_size"],
            "heads": d["num_attention_heads"], "kv_heads": d["num_key_value_heads"], "head_dim": d["head_dim"],
            "experts": d["n_routed_experts_published"], "experts_held": d["n_routed_experts"],
            "width": d["moe_intermediate_size"], "shared_width": d["n_shared_experts"] * d["moe_intermediate_size"],
            "vocab": d["vocab_size"],
        }

    # -- the job ----------------------------------------------------------------
    def fit(self) -> dict:
        """One whole fit job; returns host-side numbers only, so that nothing
        pins the fit's device arrays while the next fit allocates its own."""
        from flink_ml_tpu.models.lm import DecoderLM

        d, delta = self.dims, self.dims["linear_attn_config"]
        est = (
            DecoderLM().set_block_kind("solar_open2")  # first: a program without the kind refuses here, by name
            .set_num_layers(d["num_hidden_layers"]).set_gqa_layers(reference.attending(d))
            .set_hidden_size(d["hidden_size"])
            .set_kda_num_heads(delta["num_heads"]).set_kda_head_size(delta["head_dim"])
            .set_ssm_conv_kernel(delta["short_conv_kernel_size"]).set_ssm_chunk_size(self.cfg["chunk_size"])
            .set_num_heads(d["num_attention_heads"]).set_num_kv_heads(d["num_key_value_heads"])
            .set_head_size(d["head_dim"])
            .set_num_experts(d["n_routed_experts_published"]).set_experts_held(d["n_routed_experts"])
            .set_first_expert_held(d["first_expert_held"]).set_experts_per_token(d["num_experts_per_tok"])
            .set_expert_width(d["moe_intermediate_size"])
            .set_shared_expert_width(d["n_shared_experts"] * d["moe_intermediate_size"])
            .set_routed_scale(float(d["routed_scaling_factor"]))
            .set_vocab_size(d["vocab_size"]).set_norm_eps(float(d["rms_norm_eps"]))
            .set_compute_type(self.cfg["compute_dtype"])
            .set_max_iter(self.steps).set_global_batch_size(self.batch)
            .set_learning_rate(self.hyper["learning_rate"]).set_seed(self.seed % (2 ** 31))
        )
        model = est.fit(self.df)
        del model  # its parameters leave the device here
        loads = np.asarray(est.expert_rows_history)  # [steps, layers, published experts]
        return {
            "losses": list(est.loss_history),
            "grad_norms": list(est.grad_norm_history),
            "group_norms": dict(zip(est.param_names, est.param_grad_norm_history[0])),
            "expert_rows": loads[0],
            "steps_expected": self.steps,
            "rows_missing": int(self.steps * self.batch * self.seq_len * d["num_experts_per_tok"]
                                * loads.shape[1] - loads.sum()),
        }

    # -- the output check ---------------------------------------------------------
    def reference(self, precision: str = "f32") -> dict:
        """The head of the job from the same seed, by the plain reference on
        this device: two steps' losses, the first step's gradient norms and
        loads. A fit is a function of the seed alone, so the last completed
        fit's first two steps ARE the head of the job the reference computes."""
        gc.collect()
        lo2 = self.batch if 2 * self.batch <= self.n_seq else 0
        batches = [self.tok[: self.batch], self.tok[lo2: lo2 + self.batch]]
        out = reference.head_of_job(self.dims, self.hyper, self.seed % (2 ** 31), batches, precision)
        out.update(rows_missing=0, steps_expected=2)
        return out

    def compare(self, got: dict, want: dict) -> dict:
        """The numbers the check holds to their limits: program (or control)
        ``got`` against the float32 reference ``want``. A leaf whose reference
        gradient is exactly zero (the selection bias: it enters the choice of
        experts and nothing else) must be exactly zero on the other side.

        ``kda_grad_norm_rel_err`` is the worst relative error of the gradient
        norm over the delta rule's own leaves (``A_log``, ``dt_bias``, ``Fa``,
        ``Fb``, ``Wb`` of every such layer): their gradients sum over every
        position's decay, correction and state, so they read what precision the
        rule's decays and state were carried in. ``expert_grad_norm_bias`` is
        Laguna's: the mean SIGNED relative error of the gradient norm over the
        held experts' leaves. ``rows_changed_expert_pct`` is JoyAI's: the share,
        in per cent, of the routed rows that changed expert at step 1, as the
        two sides' loads bound it from below (half their summed absolute
        difference an expert): at one sequence a step a held expert sees about
        100 rows a layer, too few for the bias to tell a bfloat16 router."""
        def rel(a, b):
            return abs(a - b) / abs(b) if b else float(a != 0.0)

        whole = want["grad_norms"][0]
        group = {k: rel(got["group_norms"][k], v) for k, v in want["group_norms"].items()}
        worst = max(group, key=group.get)
        leaf = lambda k: k.rsplit(".", 1)[-1]  # noqa: E731
        delta = [k for k in group if leaf(k) in ("A_log", "dt_bias", "Fa", "Fb", "Wb")]
        held = [k for k in group if leaf(k) in ("w_gate", "w_up", "w_down")]
        bias = float(np.mean([(got["group_norms"][k] - want["group_norms"][k]) / want["group_norms"][k]
                              for k in held]))
        worst_delta = max(delta, key=group.get)
        table = sorted(group, key=group.get, reverse=True)[:6]
        print("check_groups " + "; ".join(f"{k} {group[k]:.2e} at {want['group_norms'][k] / whole:.1e} of the whole"
                                          for k in table), flush=True)
        print("check_group_norms " + json.dumps({k: [float(got["group_norms"][k]), v]
                                                 for k, v in want["group_norms"].items()}), flush=True)
        moved = np.abs(np.asarray(got["expert_rows"], np.int64) - want["expert_rows"]).sum()
        changed = 100.0 * float(moved) / 2 / max(1, int(np.sum(want["expert_rows"])))
        rows = np.asarray(got["expert_rows"], np.float64)
        lo = self.dims["first_expert_held"]
        share = float(rows[:, lo: lo + self.dims["n_routed_experts"]].sum() / rows.sum())
        print(f"check_detail losses {[round(x, 4) for x in got['losses']]} against {want['losses']}; "
              f"worst group {worst} {group[worst]:.3e}; worst delta-rule leaf {worst_delta} "
              f"{group[worst_delta]:.3e}; the {len(held)} held-expert leaves' mean signed {bias:+.3e}; routed rows "
              f"that changed expert at step 1 (lower bound, from the loads): {changed:.4f}%; step-1 held share "
              f"{share:.4f}",
              flush=True)
        loss = [rel(g, w) for g, w in zip(got["losses"], want["losses"])] + [float("inf")] * 2
        return {
            "loss_rel_err": loss[0],
            "loss_after_update_rel_err": loss[1],
            "grad_norm_rel_err": rel(got["grad_norms"][0], want["grad_norms"][0]),
            "group_grad_norm_rel_err": group[worst],
            "kda_grad_norm_rel_err": group[worst_delta],
            "expert_grad_norm_bias": abs(bias),
            "rows_changed_expert_pct": changed,
            "steps_missing": float(got["steps_expected"] - len(got["losses"])),
            "tokens_dropped": float(got["rows_missing"]),
        }


def create(config: dict, seed: int, n_devices: int):
    return SolarLmFit(config, seed, n_devices)
