"""System builder ``decoder_lm_fit``: ``DecoderLM`` through ``Estimator.fit``
on packed token sequences made from the seed.

The benchmark makes the inputs (the token window as a DataFrame column) and
holds the plain reference's inputs; everything between ``fit()`` and the
losses, gradient norms and expert loads it reports is the program's.
"""
from __future__ import annotations

import gc

import numpy as np

from perfbench.references import decoder_lm as reference

#: No host function of the program is wrapped: the LM fit opens its own
#: ``train.*`` phases (docs/observability.md), which the reducers read.
LAYER_SPANS = ()

#: The configuration's keys the reference's equations read, as published.
DIMS = ("num_hidden_layers", "hidden_size", "num_attention_heads", "num_experts",
        "num_experts_per_tok", "intermediate_size", "vocab_size", "rope_theta", "rms_norm_eps")


def make_tokens(seed: int, n_seq: int, seq_len: int, vocab: int, eot: int,
                median_len: float, sigma: float, zipf_alpha: float) -> np.ndarray:
    """``[n_seq, seq_len]`` int32: documents of log-normal length (median
    ``median_len``, clipped to 16..16,384 tokens), each a run of token ids
    whose rank is a bounded Pareto(``zipf_alpha``) draw over the vocabulary (a
    few ids take a large share, the rest a long tail; rank -> id by a
    permutation from the seed), ended by ``eot``, concatenated and cut into
    sequences. Every seed gives the same shapes: token ids are values."""
    rng = np.random.default_rng(seed)
    total = n_seq * seq_len
    lengths = []
    have = 0
    while have < total:
        draw = np.clip(np.exp(np.log(median_len) + sigma * rng.standard_normal(256)), 16, 16384)
        draw = draw.astype(np.int64)
        lengths.append(draw)
        have += int(draw.sum())
    ends = np.cumsum(np.concatenate(lengths))
    e = 1.0 - zipf_alpha
    rank = np.floor((1.0 + rng.random(total) * (float(vocab) ** e - 1.0)) ** (1.0 / e))
    rank = np.minimum(rank, vocab - 1).astype(np.int64) - 1  # 0 .. vocab - 2
    perm = rng.permutation(vocab)
    ids = perm[perm != eot][rank]
    ids[ends[ends <= total] - 1] = eot  # each document's last token
    return ids.reshape(n_seq, seq_len).astype(np.int32)


def import_program() -> None:
    """The program's modules this system drives, imported during ``import_s``."""
    import flink_ml_tpu.api.dataframe  # noqa: F401
    import flink_ml_tpu.models.lm  # noqa: F401


class DecoderLmFit:
    LAYER_SPANS = LAYER_SPANS

    def __init__(self, config: dict, seed: int, n_devices: int):
        self.cfg = config
        self.seed = seed
        self.n_devices = n_devices
        self.n_seq = int(config["num_sequences"])
        self.seq_len = int(config["sequence_length"])
        self.batch = int(config["global_batch_size"])
        self.steps = int(config["max_iter"])
        self.dims = {k: config[k] for k in DIMS}
        self.dims["aux_coef"] = float(config["router_aux_loss_coef"])
        self.hyper = {k: float(config[k]) for k in
                      ("learning_rate", "weight_decay", "clip_norm", "init_std")}
        self.tok = self.df = None
        # the shapes perfbench/lm_costs.py takes
        self.layout_dims = {
            "tokens": self.batch * self.seq_len, "batch": self.batch, "seq": self.seq_len,
            "layers": self.dims["num_hidden_layers"], "hidden": self.dims["hidden_size"],
            "heads": self.dims["num_attention_heads"], "experts": self.dims["num_experts"],
            "top_k": self.dims["num_experts_per_tok"], "width": self.dims["intermediate_size"],
            "vocab": self.dims["vocab_size"],
        }

    # -- set-up ---------------------------------------------------------------
    def make_data(self) -> None:
        d = self.cfg["documents"]
        self.tok = make_tokens(self.seed, self.n_seq, self.seq_len, self.dims["vocab_size"],
                               int(self.cfg["eot_token_id"]), float(d["median_tokens"]),
                               float(d["lognormal_sigma"]), float(d["token_zipf_alpha"]))

    def build(self) -> None:
        from flink_ml_tpu.api.dataframe import DataFrame

        self.df = DataFrame.from_dict({"features": self.tok})

    def rows_per_job(self) -> int:
        return self.steps * self.batch

    # -- the job ----------------------------------------------------------------
    def fit(self) -> dict:
        """One whole fit job; returns host-side numbers only, so that nothing
        pins the fit's device arrays while the next fit allocates its own."""
        from flink_ml_tpu.models.lm import DecoderLM

        d = self.dims
        est = (
            DecoderLM()
            .set_num_layers(d["num_hidden_layers"]).set_hidden_size(d["hidden_size"])
            .set_num_heads(d["num_attention_heads"]).set_num_experts(d["num_experts"])
            .set_experts_per_token(d["num_experts_per_tok"]).set_expert_width(d["intermediate_size"])
            .set_vocab_size(d["vocab_size"]).set_rope_theta(float(d["rope_theta"]))
            .set_norm_eps(float(d["rms_norm_eps"])).set_aux_loss_coef(d["aux_coef"])
            .set_compute_type(self.cfg["compute_dtype"])
            .set_max_iter(self.steps).set_global_batch_size(self.batch)
            .set_learning_rate(self.hyper["learning_rate"]).set_seed(self.seed % (2 ** 31))
        )
        model = est.fit(self.df)
        del model  # its parameters leave the device here
        loads = np.asarray(est.expert_rows_history)
        return {
            "losses": list(est.loss_history),
            "grad_norms": list(est.grad_norm_history),
            "group_norms": dict(zip(est.param_names, est.param_grad_norm_history[0])),
            "expert_rows": loads[0],
            "steps_expected": self.steps,
            "rows_missing": int(self.steps * self.batch * self.seq_len * d["num_experts_per_tok"]
                                * d["num_hidden_layers"] - loads.sum()),
        }

    def note_layout(self, span: str, result) -> None:
        """Nothing to note: the cost functions' shapes are the configuration's."""

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
        out.update(rows_missing=0, steps_expected=2)
        return out

    def compare(self, got: dict, want: dict) -> dict:
        """The numbers the check holds to their limits: program (or control)
        ``got`` against the float32 reference ``want``."""
        rel = lambda a, b: abs(a - b) / abs(b)  # noqa: E731
        group = {k: rel(got["group_norms"][k], v) for k, v in want["group_norms"].items()}
        worst = max(group, key=group.get)
        moved = np.abs(np.asarray(got["expert_rows"], np.int64) - want["expert_rows"]).sum()
        print(f"check_detail losses {[round(x, 4) for x in got['losses']]} against {want['losses']}; "
              f"worst group {worst} {group[worst]:.3e}; routed rows that changed expert at step 1 "
              f"(lower bound, from the loads): {moved / 2 / max(1, int(np.sum(want['expert_rows']))):.4%}",
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
    return DecoderLmFit(config, seed, n_devices)
