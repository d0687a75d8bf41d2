"""System builder ``ouro_lm_fit``: ``DecoderLM`` with ``blockKind`` ``ouro``
through ``Estimator.fit`` on packed token sequences made from the seed: one
pipeline stage of six dense sandwich-norm layers of an Ouro-2.6B job, run four
times over the same weights, with the embedding, the head and the exit gate.

The benchmark makes the inputs (``DecoderLmFit.make_data``) and holds the plain
reference's inputs; everything between ``fit()`` and the losses, per-pass
losses and gradient norms it reports is the program's.
"""
from __future__ import annotations

import gc
import json

from perfbench.references import ouro_lm as reference
from perfbench.systems.decoder_lm_fit import DecoderLmFit, import_program  # noqa: F401 - the harness calls it

#: The configuration's keys the reference's equations read, as the file has them.
DIMS = ("num_hidden_layers", "hidden_size", "num_attention_heads", "head_dim", "intermediate_size",
        "vocab_size", "rope_theta", "rms_norm_eps", "total_ut_steps", "exit_entropy_coef")


class OuroLmFit(DecoderLmFit):
    """``DecoderLmFit``'s data, DataFrame and job size; this configuration's
    sizes, estimator, reference and check."""

    def __init__(self, config: dict, seed: int, n_devices: int):
        self.cfg = config
        self.seed = seed
        self.n_devices = n_devices
        self.n_seq = int(config["num_sequences"])
        self.seq_len = int(config["sequence_length"])
        self.batch = int(config["global_batch_size"])
        self.steps = int(config["max_iter"])
        self.dims = {k: config[k] for k in DIMS}
        for key, want in (("num_key_value_heads", config["num_attention_heads"]), ("tie_word_embeddings", False),
                          ("hidden_act", "silu"), ("early_exit_threshold", 1), ("use_sliding_window", False)):
            if config[key] != want:
                raise ValueError(f"the ouro block is written for {key} = {want}, the configuration has {config[key]}")
        if config["hidden_size"] != config["num_attention_heads"] * config["head_dim"]:
            raise ValueError("the ouro block's heads divide the hidden size evenly")
        self.hyper = {k: float(config[k]) for k in ("learning_rate", "weight_decay", "clip_norm", "init_std")}
        self.tok = self.df = None
        d = self.dims
        # the shapes perfbench/ouro_costs.py takes
        self.layout_dims = {
            "tokens": self.batch * self.seq_len, "batch": self.batch, "seq": self.seq_len,
            "layers": d["num_hidden_layers"], "loops": d["total_ut_steps"], "hidden": d["hidden_size"],
            "heads": d["num_attention_heads"], "head_dim": d["head_dim"], "width": d["intermediate_size"],
            "vocab": d["vocab_size"],
        }

    # -- the job ----------------------------------------------------------------
    def fit(self) -> dict:
        """One whole fit job; returns host-side numbers only, so that nothing
        pins the fit's device arrays while the next fit allocates its own."""
        from flink_ml_tpu.models.lm import DecoderLM

        d = self.dims
        est = (
            DecoderLM().set_block_kind("ouro")
            .set_num_layers(d["num_hidden_layers"]).set_hidden_size(d["hidden_size"])
            .set_num_heads(d["num_attention_heads"]).set_expert_width(d["intermediate_size"])
            .set_vocab_size(d["vocab_size"]).set_rope_theta(float(d["rope_theta"]))
            .set_norm_eps(float(d["rms_norm_eps"])).set_num_loops(d["total_ut_steps"])
            .set_exit_entropy_coef(float(d["exit_entropy_coef"]))
            .set_compute_type(self.cfg["compute_dtype"])
            .set_max_iter(self.steps).set_global_batch_size(self.batch)
            .set_learning_rate(self.hyper["learning_rate"]).set_seed(self.seed % (2 ** 31))
        )
        model = est.fit(self.df)
        del model  # its parameters leave the device here
        return {
            "losses": list(est.loss_history),
            "trip_losses": [float(x) for x in est.trip_loss_history[0]],
            "grad_norms": list(est.grad_norm_history),
            "group_norms": dict(zip(est.param_names, est.param_grad_norm_history[0])),
            "steps_expected": self.steps,
        }

    # -- the output check ---------------------------------------------------------
    def reference(self, precision: str = "f32") -> dict:
        """The head of the job from the same seed, by the plain reference on
        this device: two steps' losses, the first step's per-pass losses and
        gradient norms. A fit is a function of the seed alone, so the last
        completed fit's first two steps ARE the head of the job the reference
        computes."""
        gc.collect()
        lo2 = self.batch if 2 * self.batch <= self.n_seq else 0
        batches = [self.tok[: self.batch], self.tok[lo2: lo2 + self.batch]]
        out = reference.head_of_job(self.dims, self.hyper, self.seed % (2 ** 31), batches, precision)
        out.update(steps_expected=2)
        return out

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
        trips = [rel(g, w) for g, w in zip(got["trip_losses"], want["trip_losses"])]
        trips += [float("inf")] * (len(want["trip_losses"]) - len(trips))  # a pass the program did not report
        print(f"check_detail losses {[round(x, 4) for x in got['losses']]} against {want['losses']}; per-pass "
              f"losses {[round(x, 4) for x in got['trip_losses']]} against {want['trip_losses']} "
              f"({', '.join(f'{x:.2e}' for x in trips)}); worst group {worst} {group[worst]:.3e}", flush=True)
        loss = [rel(g, w) for g, w in zip(got["losses"], want["losses"])] + [float("inf")] * 2
        return {
            "loss_rel_err": loss[0],
            "loss_after_update_rel_err": loss[1],
            "grad_norm_rel_err": rel(got["grad_norms"][0], want["grad_norms"][0]),
            "group_grad_norm_rel_err": group[worst],
            "trip_loss_rel_err": max(trips),
            "steps_missing": float(got["steps_expected"] - len(got["losses"])),
        }


def create(config: dict, seed: int, n_devices: int):
    return OuroLmFit(config, seed, n_devices)
