"""System builder ``joyai_lm_fit``: ``DecoderLM`` with ``blockKind`` ``joyai``
through ``Estimator.fit`` on packed token sequences made from the seed: one
chip's share of an expert-parallel, vocabulary-parallel JoyAI-LLM-Flash job
(the experts held here, the slice of the untied embedding and head held here,
latent attention, the dense layer and the shared expert whole), the leading
dense layer, four expert layers and the multi-token-prediction module that
follows the stack.

The benchmark makes the inputs (``DecoderLmFit.make_data``, over the
vocabulary slice) and holds the plain reference's inputs; everything between
``fit()`` and the losses, gradient norms and expert loads it reports is the
program's.
"""
from __future__ import annotations

import gc
import json

import numpy as np

from perfbench.references import joyai_lm as reference
from perfbench.systems.decoder_lm_fit import DecoderLmFit, import_program  # noqa: F401 - the harness calls it

#: The configuration's keys the reference's equations read, as the file has them.
DIMS = ("num_hidden_layers", "hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "rope_theta", "intermediate_size", "first_k_dense_replace",
        "n_routed_experts", "n_routed_experts_published", "first_expert_held", "num_experts_per_tok",
        "moe_intermediate_size", "n_shared_experts", "routed_scaling_factor", "num_nextn_predict_layers",
        "mtp_loss_coef", "vocab_size", "rms_norm_eps")


def _dims(config: dict) -> dict:
    dims = {k: config[k] for k in DIMS}
    for key, want in (("tie_word_embeddings", False), ("attention_bias", False), ("rope_interleave", True),
                      ("rope_scaling", None), ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                      ("norm_topk_prob", True), ("n_group", 1), ("topk_group", 1), ("n_shared_experts", 1),
                      ("moe_layer_freq", 1), ("hidden_act", "silu"),
                      ("qk_head_dim", config["qk_nope_head_dim"] + config["qk_rope_head_dim"]),
                      ("num_key_value_heads", config["num_attention_heads"])):
        if config[key] != want:
            raise ValueError(f"the joyai block is written for {key} = {want}, the configuration has {config[key]}")
    if dims["num_nextn_predict_layers"] not in (0, 1):
        raise ValueError("the joyai block runs one multi-token-prediction module or none")
    return dims


def lm_config(config: dict):
    """The program's ``LMConfig`` of this configuration (the tests size the
    parameter tree from it without a fit)."""
    from flink_ml_tpu.models.lm.config import LMConfig

    d = _dims(config)
    return LMConfig(
        d["num_hidden_layers"], d["hidden_size"], d["num_attention_heads"], d["n_routed_experts_published"],
        d["num_experts_per_tok"], d["moe_intermediate_size"], d["vocab_size"], rope_theta=float(d["rope_theta"]),
        norm_eps=float(d["rms_norm_eps"]), aux_coef=0.0, block="joyai", experts_held=d["n_routed_experts"],
        first_held=d["first_expert_held"], n_dense=d["first_k_dense_replace"], dense_width=d["intermediate_size"],
        shared_width=d["moe_intermediate_size"] * d["n_shared_experts"],
        routed_scale=float(d["routed_scaling_factor"]), q_rank=d["q_lora_rank"], kv_rank=d["kv_lora_rank"],
        nope_dim=d["qk_nope_head_dim"], rope_dim=d["qk_rope_head_dim"], v_dim=d["v_head_dim"],
        mtp_depth=d["num_nextn_predict_layers"], mtp_coef=float(d["mtp_loss_coef"]))


class JoyaiLmFit(DecoderLmFit):
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
        # the shapes perfbench/joyai_costs.py takes
        self.layout_dims = {
            "tokens": self.batch * self.seq_len, "batch": self.batch, "seq": self.seq_len,
            "hidden": d["hidden_size"], "layers": d["num_hidden_layers"], "dense_layers": d["first_k_dense_replace"],
            "dense_width": d["intermediate_size"], "heads": d["num_attention_heads"], "q_rank": d["q_lora_rank"],
            "kv_rank": d["kv_lora_rank"], "nope_dim": d["qk_nope_head_dim"], "rope_dim": d["qk_rope_head_dim"],
            "v_dim": d["v_head_dim"], "experts": d["n_routed_experts_published"],
            "experts_held": d["n_routed_experts"], "width": d["moe_intermediate_size"],
            "shared_width": d["moe_intermediate_size"] * d["n_shared_experts"],
            "mtp_depth": d["num_nextn_predict_layers"], "vocab": d["vocab_size"],
        }

    # -- the job ----------------------------------------------------------------
    def fit(self) -> dict:
        """One whole fit job; returns host-side numbers only, so that nothing
        pins the fit's device arrays while the next fit allocates its own."""
        from flink_ml_tpu.models.lm import DecoderLM

        d = self.dims
        est = (
            DecoderLM().set_block_kind("joyai")  # first: a program without the kind refuses here, by name
            .set_num_layers(d["num_hidden_layers"]).set_hidden_size(d["hidden_size"])
            .set_num_heads(d["num_attention_heads"])
            .set_q_lora_rank(d["q_lora_rank"]).set_kv_lora_rank(d["kv_lora_rank"])
            .set_qk_nope_head_size(d["qk_nope_head_dim"]).set_qk_rope_head_size(d["qk_rope_head_dim"])
            .set_v_head_size(d["v_head_dim"]).set_rope_theta(float(d["rope_theta"]))
            .set_dense_layers(d["first_k_dense_replace"]).set_dense_width(d["intermediate_size"])
            .set_num_experts(d["n_routed_experts_published"]).set_experts_held(d["n_routed_experts"])
            .set_first_expert_held(d["first_expert_held"]).set_experts_per_token(d["num_experts_per_tok"])
            .set_expert_width(d["moe_intermediate_size"])
            .set_shared_expert_width(d["moe_intermediate_size"] * d["n_shared_experts"])
            .set_routed_scale(float(d["routed_scaling_factor"]))
            .set_mtp_depth(d["num_nextn_predict_layers"]).set_mtp_loss_coef(float(d["mtp_loss_coef"]))
            .set_vocab_size(d["vocab_size"]).set_norm_eps(float(d["rms_norm_eps"]))
            .set_compute_type(self.cfg["compute_dtype"])
            .set_max_iter(self.steps).set_global_batch_size(self.batch)
            .set_learning_rate(self.hyper["learning_rate"]).set_seed(self.seed % (2 ** 31))
        )
        model = est.fit(self.df)
        del model  # its parameters leave the device here
        loads = np.asarray(est.expert_rows_history)  # [steps, expert layers with the module's last, published experts]
        return {
            "losses": list(est.loss_history),
            "mtp_losses": list(est.mtp_loss_history),
            "grad_norms": list(est.grad_norm_history),
            "group_norms": dict(zip(est.param_names, est.param_grad_norm_history[0])),
            "expert_rows": loads[0],
            "steps_expected": self.steps,
            # the module's layer runs all T positions, its filler at the last among them: its 8 rows a sequence
            # are routed, counted and expected like any other's
            "rows_missing": int(self.steps * self.batch * self.seq_len * d["num_experts_per_tok"]
                                * loads.shape[1] - loads.sum()),
        }

    # -- the output check ---------------------------------------------------------
    def reference(self, precision: str = "f32") -> dict:
        """The head of the job from the same seed, by the plain reference on
        this device: two steps' losses, the first step's module loss, gradient
        norms and loads. A fit is a function of the seed alone, so the last
        completed fit's first two steps ARE the head of the job the reference
        computes."""
        gc.collect()
        lo2 = self.batch if 2 * self.batch <= self.n_seq else 0
        batches = [self.tok[: self.batch], self.tok[lo2: lo2 + self.batch]]
        out = reference.head_of_job(self.dims, self.hyper, self.seed % (2 ** 31), batches, precision)
        out.update(rows_missing=0, steps_expected=2)
        return out

    def held_share(self, rows) -> tuple:
        """Of one step's loads ``[expert layers, published experts]``: the share
        of the routed rows whose expert is held here, and the fullest held
        expert over the held mean."""
        rows = np.asarray(rows, np.float64)
        lo = self.dims["first_expert_held"]
        held = rows[:, lo: lo + self.dims["n_routed_experts"]]
        return float(held.sum() / rows.sum()), float(held.max() / held.mean())

    def compare(self, got: dict, want: dict) -> dict:
        """The numbers the check holds to their limits: program (or control)
        ``got`` against the float32 reference ``want``. A leaf whose reference
        gradient is exactly zero (the selection bias: it enters the choice of
        experts and nothing else) must be exactly zero on the other side.

        The program's matmul inputs are bfloat16, so a share of the routed rows
        changes expert against the float32 reference, and an expert layer's
        router and held experts move in steps with them (one layer's router by
        0.39 on one seed where the others read 0.02-0.17): those 20 leaves
        are held apart from the 84 that no row's choice moves.
        ``group_grad_norm_rel_err`` is the worst of the 84 (the latent leaves and
        the module's among them). ``routed_grad_norm_rel_err`` takes each expert
        layer's worst of its router and its three held-expert leaves, and of the
        five layers the MEDIAN: what is wrong in the code a layer runs shows in
        every layer that runs it, a row that changed expert in one.
        ``expert_grad_norm_bias`` is Laguna's: the mean, over the held experts'
        leaves (``w_gate``, ``w_up``, ``w_down`` of every expert layer, the
        module's among them), of the SIGNED relative error of the gradient's
        norm. ``rows_changed_expert_pct`` is the share itself, in the stack's
        layers at step 1, as the loads bound it from below: a router that did not
        run in float32 reads twice the sound program's.

        ``mtp_loss_rel_err`` is the module's OWN mean loss at step 1: a module
        left out, or scored on the wrong token, fails it whatever the 0.3 hides
        in the sum."""
        def rel(a, b):
            return abs(a - b) / abs(b) if b else float(a != 0.0)

        whole = want["grad_norms"][0]
        group = {k: rel(got["group_norms"][k], v) for k, v in want["group_norms"].items()}
        expert_layers = sorted(k.rsplit(".", 1)[0] for k in group if k.endswith(".router"))
        held = [f"{layer}.{leaf}" for layer in expert_layers for leaf in ("w_gate", "w_up", "w_down")]
        routed = {layer: max(group[f"{layer}.{leaf}"] for leaf in ("router", "w_gate", "w_up", "w_down"))
                  for layer in expert_layers}
        rest = {k: v for k, v in group.items() if k not in held and not k.endswith(".router")}
        worst = max(rest, key=rest.get)
        bias = float(np.mean([(got["group_norms"][k] - want["group_norms"][k]) / want["group_norms"][k]
                              for k in held]))
        table = sorted(group, key=group.get, reverse=True)[:6]
        print("check_groups " + "; ".join(f"{k} {group[k]:.2e} at {want['group_norms'][k] / whole:.1e} of the whole"
                                          for k in table), flush=True)
        print("check_group_norms " + json.dumps({k: [float(got["group_norms"][k]), v]
                                                 for k, v in want["group_norms"].items()}), flush=True)
        # the stack's layers alone: the module's loads hold its filler's rows on the program's side
        stack = self.dims["num_hidden_layers"] - self.dims["first_k_dense_replace"]
        moved = np.abs(np.asarray(got["expert_rows"], np.int64)[:stack] - want["expert_rows"][:stack]).sum(axis=1)
        changed = 100.0 * float(moved.sum()) / 2 / max(1, int(np.sum(want["expert_rows"][:stack])))
        share, fullest = self.held_share(got["expert_rows"])
        print(f"check_detail losses {[round(x, 4) for x in got['losses']]} against {want['losses']}; the module's own "
              f"{[round(x, 4) for x in got['mtp_losses'][:1]]} against {want['mtp_losses']}; "
              f"worst leaf no row's choice moves {worst} {rest[worst]:.3e}; the expert layers' worst routed leaf "
              f"{[round(float(v), 4) for v in routed.values()]}, "
              f"the {len(held)} held-expert leaves' mean signed {bias:+.3e}; "
              f"routed rows of the stack's layers that changed expert at step 1 "
              f"(lower bound, from the loads): {changed:.4f}%, a layer {[int(m) // 2 for m in moved]}; "
              f"step-1 held share {share:.4f}, fullest held expert over the held mean {fullest:.3f}",
              flush=True)
        loss = [rel(g, w) for g, w in zip(got["losses"], want["losses"])] + [float("inf")] * 2
        ahead = [rel(g, w) for g, w in zip(got["mtp_losses"], want["mtp_losses"])] + [float("inf")]
        return {
            "loss_rel_err": loss[0],
            "loss_after_update_rel_err": loss[1],
            "mtp_loss_rel_err": ahead[0],
            "grad_norm_rel_err": rel(got["grad_norms"][0], want["grad_norms"][0]),
            "group_grad_norm_rel_err": rest[worst],
            "routed_grad_norm_rel_err": float(np.median(list(routed.values()))),
            "expert_grad_norm_bias": abs(bias),
            "rows_changed_expert_pct": changed,
            "steps_missing": float(got["steps_expected"] - len(got["losses"])),
            "tokens_dropped": float(got["rows_missing"]),
        }


def create(config: dict, seed: int, n_devices: int):
    return JoyaiLmFit(config, seed, n_devices)
