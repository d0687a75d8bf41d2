"""System builder ``zaya_lm_fit``: ``DecoderLM`` with ``blockKind`` ``zaya``
through ``Estimator.fit`` on packed token sequences made from the seed: one
chip's share of an expert-parallel, vocabulary-parallel ZAYA1-8B job (the
experts held here, the slice of the tied table held here).

The benchmark makes the inputs (``DecoderLmFit.make_data``, over the
vocabulary slice) and holds the plain reference's inputs; everything between
``fit()`` and the losses, gradient norms and expert loads it reports is the
program's.
"""
from __future__ import annotations

import gc
import json

import numpy as np

from perfbench.references import zaya_lm as reference
from perfbench.systems.decoder_lm_fit import DecoderLmFit, import_program  # noqa: F401 - the harness calls it

#: The configuration's keys the reference's equations read, as the file has them.
DIMS = ("num_hidden_layers", "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
        "num_experts", "num_experts_published", "first_expert_held", "num_experts_per_tok",
        "moe_intermediate_size", "router_hidden_size", "vocab_size", "partial_rotary_factor", "rms_norm_eps")


class ZayaLmFit(DecoderLmFit):
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
        self.dims = {k: config[k] for k in DIMS}
        self.dims["rope_theta"] = float(config["rope_parameters"]["hybrid"]["rope_theta"])
        for key, want in (("cca_time0", 2), ("cca_time1", 2), ("num_experts_per_tok", 1),
                          ("tie_word_embeddings", True), ("num_key_value_heads", 2)):
            if config[key] != want:
                raise ValueError(f"the zaya block is written for {key} = {want}, the configuration has {config[key]}")
        self.hyper = {k: float(config[k]) for k in
                      ("learning_rate", "weight_decay", "clip_norm", "init_std", "out_proj_init_scale")}
        self.tok = self.df = None
        d = self.dims
        # the shapes perfbench/zaya_costs.py takes
        self.layout_dims = {
            "tokens": self.batch * self.seq_len, "batch": self.batch, "seq": self.seq_len,
            "layers": d["num_hidden_layers"], "hidden": d["hidden_size"],
            "heads": d["num_attention_heads"], "kv_heads": d["num_key_value_heads"], "head_dim": d["head_dim"],
            "experts_held": d["num_experts"], "experts": d["num_experts_published"],
            "width": d["moe_intermediate_size"], "router_width": d["router_hidden_size"],
            "vocab": d["vocab_size"],
        }

    # -- the job ----------------------------------------------------------------
    def fit(self) -> dict:
        """One whole fit job; returns host-side numbers only, so that nothing
        pins the fit's device arrays while the next fit allocates its own."""
        from flink_ml_tpu.models.lm import DecoderLM

        d = self.dims
        est = (
            DecoderLM().set_block_kind("zaya")
            .set_num_layers(d["num_hidden_layers"]).set_hidden_size(d["hidden_size"])
            .set_num_heads(d["num_attention_heads"]).set_num_kv_heads(d["num_key_value_heads"])
            .set_head_size(d["head_dim"]).set_rope_fraction(float(d["partial_rotary_factor"]))
            .set_rope_theta(d["rope_theta"]).set_router_width(d["router_hidden_size"])
            .set_num_experts(d["num_experts_published"]).set_experts_held(d["num_experts"])
            .set_first_expert_held(d["first_expert_held"]).set_experts_per_token(d["num_experts_per_tok"])
            .set_expert_width(d["moe_intermediate_size"]).set_vocab_size(d["vocab_size"])
            .set_tie_embeddings(True).set_norm_eps(float(d["rms_norm_eps"]))
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
                                * d["num_hidden_layers"] - loads.sum()),
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
        """Of one step's loads ``[layers, published experts]``: the share of the
        routed rows whose expert is held here, and the fullest held expert over
        the held mean."""
        rows = np.asarray(rows, np.float64)
        lo = self.dims["first_expert_held"]
        held = rows[:, lo: lo + self.dims["num_experts"]]
        return float(held.sum() / rows.sum()), float(held.max() / held.mean())

    def compare(self, got: dict, want: dict) -> dict:
        """The numbers the check holds to their limits: program (or control)
        ``got`` against the float32 reference ``want``."""
        rel = lambda a, b: abs(a - b) / abs(b)  # noqa: E731
        whole = want["grad_norms"][0]
        group = {k: rel(got["group_norms"][k], v) for k, v in want["group_norms"].items()}
        worst = max(group, key=group.get)
        table = sorted(group, key=group.get, reverse=True)[:6]
        print("check_groups " + "; ".join(f"{k} {group[k]:.2e} at {want['group_norms'][k] / whole:.1e} of the whole"
                                          for k in table), flush=True)
        print("check_group_norms " + json.dumps({k: [float(got["group_norms"][k]), v]
                                                 for k, v in want["group_norms"].items()}), flush=True)
        moved = np.abs(np.asarray(got["expert_rows"], np.int64) - want["expert_rows"]).sum()
        share, fullest = self.held_share(got["expert_rows"])
        print(f"check_detail losses {[round(x, 4) for x in got['losses']]} against {want['losses']}; "
              f"worst group {worst} {group[worst]:.3e}; routed rows that changed expert at step 1 "
              f"(lower bound, from the loads): {moved / 2 / max(1, int(np.sum(want['expert_rows']))):.4%}; "
              f"step-1 held share {share:.4f}, fullest held expert over the held mean {fullest:.3f}",
              flush=True)
        loss = [rel(g, w) for g, w in zip(got["losses"], want["losses"])] + [float("inf")] * 2
        return {
            "loss_rel_err": loss[0],
            "loss_after_update_rel_err": loss[1],
            "grad_norm_rel_err": rel(got["grad_norms"][0], want["grad_norms"][0]),
            "group_grad_norm_rel_err": group[worst],
            "steps_missing": float(got["steps_expected"] - len(got["losses"])),
            "tokens_dropped": float(got["rows_missing"]),
        }


def create(config: dict, seed: int, n_devices: int):
    return ZayaLmFit(config, seed, n_devices)
