"""System builder ``laguna_lm_fit``: ``DecoderLM`` with ``blockKind``
``laguna`` through ``Estimator.fit`` on packed token sequences made from the
seed: one chip's share of an expert-parallel, vocabulary-parallel Laguna-XS.2
job (the experts held here, the slice of the untied embedding and head held
here, attention and the shared expert whole), the leading dense layer and one
whole period of windowed and full layers.

The benchmark makes the inputs (``DecoderLmFit.make_data``, over the
vocabulary slice) and holds the plain reference's inputs; everything between
``fit()`` and the losses, gradient norms and expert loads it reports is the
program's. Per-layer lists (``layer_types``, ``mlp_layer_types``,
``num_attention_heads_per_layer``) stay as published, 40 entries: the first
``num_hidden_layers`` of them are the layers that run.
"""
from __future__ import annotations

import gc
import json

import numpy as np

from perfbench.references import laguna_lm as reference
from perfbench.systems.decoder_lm_fit import DecoderLmFit, import_program  # noqa: F401 - the harness calls it

#: The configuration's keys the reference's equations read, as the file has them.
DIMS = ("num_hidden_layers", "hidden_size", "num_key_value_heads", "head_dim", "intermediate_size",
        "num_experts", "num_experts_published", "first_expert_held", "num_experts_per_tok",
        "moe_intermediate_size", "shared_expert_intermediate_size", "moe_routed_scaling_factor", "vocab_size",
        "rms_norm_eps", "sliding_window", "layer_types", "mlp_layer_types", "num_attention_heads_per_layer")
YARN = ("factor", "original_max_position_embeddings", "beta_fast", "beta_slow", "attention_factor")


def _dims(config: dict) -> dict:
    dims = {k: config[k] for k in DIMS}
    dims["rope_full"] = config["rope_parameters"]["full_attention"]
    dims["rope_sliding"] = config["rope_parameters"]["sliding_attention"]
    for key, want in (("tie_word_embeddings", False), ("gating", True), ("attention_bias", False),
                      ("moe_apply_router_weight_on_input", False)):
        if config[key] != want:
            raise ValueError(f"the laguna block is written for {key} = {want}, the configuration has {config[key]}")
    if dims["rope_full"]["rope_type"] != "yarn" or dims["rope_sliding"]["rope_type"] != "default":
        raise ValueError("the laguna block turns its full layers by YaRN and its windowed layers by plain RoPE")
    if set(config["mlp_layer_types"][: config["num_hidden_layers"]]) - {"dense", "sparse"}:
        raise ValueError("mlp_layer_types holds 'dense' and 'sparse'")
    return dims


def lm_config(config: dict):
    """The program's ``LMConfig`` of this configuration (the tests size the
    parameter tree from it without a fit)."""
    from flink_ml_tpu.models.lm.config import LMConfig

    d = _dims(config)
    kinds = reference.layer_kinds(d)
    dense = [is_dense for _, _, is_dense in kinds]
    if dense != sorted(dense, reverse=True):
        raise ValueError("the laguna stack's dense layers lead")
    full = d["rope_full"]
    return LMConfig(
        d["num_hidden_layers"], d["hidden_size"], config["num_attention_heads"], d["num_experts_published"],
        d["num_experts_per_tok"], d["moe_intermediate_size"], d["vocab_size"], rope_theta=float(full["rope_theta"]),
        norm_eps=float(d["rms_norm_eps"]), aux_coef=0.0, block="laguna", experts_held=d["num_experts"],
        first_held=d["first_expert_held"], n_kv_heads=d["num_key_value_heads"], head_size=d["head_dim"],
        rope_fraction=float(full["partial_rotary_factor"]), layer_heads=tuple(h for h, _, _ in kinds),
        layer_windows=tuple(w for _, w, _ in kinds), n_dense=sum(dense), dense_width=d["intermediate_size"],
        shared_width=d["shared_expert_intermediate_size"], routed_scale=float(d["moe_routed_scaling_factor"]),
        window_rope_theta=float(d["rope_sliding"]["rope_theta"]), yarn=tuple(float(full[k]) for k in YARN))


class LagunaLmFit(DecoderLmFit):
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
        kinds = reference.layer_kinds(d)
        # the shapes perfbench/laguna_costs.py takes
        self.layout_dims = {
            "tokens": self.batch * self.seq_len, "batch": self.batch, "seq": self.seq_len,
            "hidden": d["hidden_size"], "kv_heads": d["num_key_value_heads"], "head_dim": d["head_dim"],
            "layer_heads": [h for h, _, _ in kinds], "layer_windows": [w for _, w, _ in kinds],
            "dense_layers": sum(is_dense for _, _, is_dense in kinds), "dense_width": d["intermediate_size"],
            "experts": d["num_experts_published"], "experts_held": d["num_experts"],
            "width": d["moe_intermediate_size"], "shared_width": d["shared_expert_intermediate_size"],
            "vocab": d["vocab_size"],
        }

    # -- the job ----------------------------------------------------------------
    def fit(self) -> dict:
        """One whole fit job; returns host-side numbers only, so that nothing
        pins the fit's device arrays while the next fit allocates its own."""
        from flink_ml_tpu.models.lm import DecoderLM

        d, full = self.dims, self.dims["rope_full"]
        kinds = reference.layer_kinds(d)
        est = (
            DecoderLM().set_block_kind("laguna")  # first: a program without the kind refuses here, by name
            .set_num_layers(d["num_hidden_layers"]).set_hidden_size(d["hidden_size"])
            .set_num_heads(self.cfg["num_attention_heads"]).set_num_kv_heads(d["num_key_value_heads"])
            .set_head_size(d["head_dim"])
            .set_num_heads_per_layer([h for h, _, _ in kinds]).set_window_per_layer([w for _, w, _ in kinds])
            .set_rope_theta(float(full["rope_theta"])).set_rope_fraction(float(full["partial_rotary_factor"]))
            .set_rope_yarn([float(full[k]) for k in YARN])
            .set_window_rope_theta(float(d["rope_sliding"]["rope_theta"]))
            .set_dense_layers(sum(is_dense for _, _, is_dense in kinds)).set_dense_width(d["intermediate_size"])
            .set_num_experts(d["num_experts_published"]).set_experts_held(d["num_experts"])
            .set_first_expert_held(d["first_expert_held"]).set_experts_per_token(d["num_experts_per_tok"])
            .set_expert_width(d["moe_intermediate_size"])
            .set_shared_expert_width(d["shared_expert_intermediate_size"])
            .set_routed_scale(float(d["moe_routed_scaling_factor"]))
            .set_vocab_size(d["vocab_size"]).set_norm_eps(float(d["rms_norm_eps"]))
            .set_compute_type(self.cfg["compute_dtype"])
            .set_max_iter(self.steps).set_global_batch_size(self.batch)
            .set_learning_rate(self.hyper["learning_rate"]).set_seed(self.seed % (2 ** 31))
        )
        model = est.fit(self.df)
        del model  # its parameters leave the device here
        loads = np.asarray(est.expert_rows_history)  # [steps, sparse layers, published experts]
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

    def held_share(self, rows) -> tuple:
        """Of one step's loads ``[sparse layers, published experts]``: the share
        of the routed rows whose expert is held here, and the fullest held
        expert over the held mean."""
        rows = np.asarray(rows, np.float64)
        lo = self.dims["first_expert_held"]
        held = rows[:, lo: lo + self.dims["num_experts"]]
        return float(held.sum() / rows.sum()), float(held.max() / held.mean())

    def compare(self, got: dict, want: dict) -> dict:
        """The numbers the check holds to their limits: program (or control)
        ``got`` against the float32 reference ``want``. A leaf whose reference
        gradient is exactly zero (the selection bias: it enters the choice of
        experts and nothing else) must be exactly zero on the other side.

        ``expert_grad_norm_bias`` is the mean, over the held experts' leaves
        (``w_gate``, ``w_up``, ``w_down`` of every sparse layer), of the SIGNED
        relative error of the gradient's norm. A held expert's gradient is
        small, so rounding noise, which adds to a norm in quadrature, reads
        every one of these leaves high in a computation a precision lower; a
        token whose eighth expert flips moves a layer's three leaves either
        way, in the sound program too, and the mean over the layers cancels
        that and keeps the bias."""
        def rel(a, b):
            return abs(a - b) / abs(b) if b else float(a != 0.0)

        whole = want["grad_norms"][0]
        group = {k: rel(got["group_norms"][k], v) for k, v in want["group_norms"].items()}
        worst = max(group, key=group.get)
        held = [k for k in group if k.rsplit(".", 1)[-1] in ("w_gate", "w_up", "w_down")
                and k.rsplit(".", 1)[0] + ".router" in group]
        bias = float(np.mean([(got["group_norms"][k] - want["group_norms"][k]) / want["group_norms"][k]
                              for k in held]))
        table = sorted(group, key=group.get, reverse=True)[:6]
        print("check_groups " + "; ".join(f"{k} {group[k]:.2e} at {want['group_norms'][k] / whole:.1e} of the whole"
                                          for k in table), flush=True)
        print("check_group_norms " + json.dumps({k: [float(got["group_norms"][k]), v]
                                                 for k, v in want["group_norms"].items()}), flush=True)
        moved = np.abs(np.asarray(got["expert_rows"], np.int64) - want["expert_rows"]).sum()
        share, fullest = self.held_share(got["expert_rows"])
        print(f"check_detail losses {[round(x, 4) for x in got['losses']]} against {want['losses']}; "
              f"worst group {worst} {group[worst]:.3e}, the {len(held)} held-expert leaves' mean signed {bias:+.3e}; "
              f"routed rows that changed expert at step 1 "
              f"(lower bound, from the loads): {moved / 2 / max(1, int(np.sum(want['expert_rows']))):.4%}; "
              f"step-1 held share {share:.4f}, fullest held expert over the held mean {fullest:.3f}",
              flush=True)
        loss = [rel(g, w) for g, w in zip(got["losses"], want["losses"])] + [float("inf")] * 2
        return {
            "loss_rel_err": loss[0],
            "loss_after_update_rel_err": loss[1],
            "grad_norm_rel_err": rel(got["grad_norms"][0], want["grad_norms"][0]),
            "group_grad_norm_rel_err": group[worst],
            "expert_grad_norm_bias": abs(bias),
            "steps_missing": float(got["steps_expected"] - len(got["losses"])),
            "tokens_dropped": float(got["rows_missing"]),
        }


def create(config: dict, seed: int, n_devices: int):
    return LagunaLmFit(config, seed, n_devices)
