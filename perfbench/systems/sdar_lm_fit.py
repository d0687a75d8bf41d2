"""System builder ``sdar_lm_fit``: ``DecoderLM`` with ``blockKind`` ``sdar``
through ``Estimator.fit`` on packed token sequences made from the seed: one
chip's share of an expert-parallel, vocabulary-parallel SDAR-30B-A3B-Chat job
trained by block diffusion (the experts held here, the slice of the untied
embedding and head held here, attention whole): each step masks its sequences'
tokens on the device, runs the stack once over the doubled sequence ``[x ;
x~]`` under the block-diffusion mask and scores the masked positions.

The benchmark makes the inputs (``DecoderLmFit.make_data``, over the
vocabulary slice's ids that are not the mask) and holds the plain reference's
inputs; everything between ``fit()`` and the losses, gradient norms, expert
loads and scored positions it reports is the program's.
"""
from __future__ import annotations

import gc
import json

import numpy as np

from perfbench.references import sdar_lm as reference
from perfbench.systems.decoder_lm_fit import DecoderLmFit, import_program, make_tokens  # noqa: F401 - the harness calls it

#: The configuration's keys the reference's equations read, as the file has them.
DIMS = ("num_hidden_layers", "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta",
        "num_experts", "num_experts_published", "first_expert_held", "num_experts_per_tok", "moe_intermediate_size",
        "vocab_size", "rms_norm_eps", "block_length", "mask_token_id", "noise_eps")


def _dims(config: dict) -> dict:
    dims = {k: config[k] for k in DIMS}
    for key, want in (("tie_word_embeddings", False), ("attention_bias", False), ("rope_scaling", None),
                      ("norm_topk_prob", True), ("decoder_sparse_step", 1), ("mlp_only_layers", []),
                      ("use_sliding_window", False), ("sliding_window", None), ("hidden_act", "silu"),
                      ("mask_token_id", config["vocab_size"] - 1), ("noise_eps", 0.001)):
        if config[key] != want:
            raise ValueError(f"the sdar block is written for {key} = {want}, the configuration has {config[key]}")
    return dims


def lm_config(config: dict):
    """The program's ``LMConfig`` of this configuration (the tests size the
    parameter tree from it without a fit)."""
    from flink_ml_tpu.models.lm.config import LMConfig

    d = _dims(config)
    return LMConfig(
        d["num_hidden_layers"], d["hidden_size"], d["num_attention_heads"], d["num_experts_published"],
        d["num_experts_per_tok"], d["moe_intermediate_size"], d["vocab_size"], rope_theta=float(d["rope_theta"]),
        norm_eps=float(d["rms_norm_eps"]), aux_coef=0.0, block="sdar", experts_held=d["num_experts"],
        first_held=d["first_expert_held"], n_kv_heads=d["num_key_value_heads"], head_size=d["head_dim"],
        block_length=d["block_length"], mask_id=d["mask_token_id"])


class SdarLmFit(DecoderLmFit):
    """``DecoderLmFit``'s DataFrame and job size; this configuration's data
    (no document token is the mask's id), sizes, estimator, reference and
    check."""

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
        # the shapes perfbench/sdar_costs.py takes
        self.layout_dims = {
            "tokens": self.batch * self.seq_len, "batch": self.batch, "seq": self.seq_len,
            "block": d["block_length"], "hidden": d["hidden_size"], "layers": d["num_hidden_layers"],
            "heads": d["num_attention_heads"], "kv_heads": d["num_key_value_heads"], "head_dim": d["head_dim"],
            "experts": d["num_experts_published"], "experts_held": d["num_experts"],
            "width": d["moe_intermediate_size"], "vocab": d["vocab_size"],
        }

    def make_data(self) -> None:
        """``make_tokens`` over the slice's ids that are not the mask: the
        generator draws from ``vocab_size - 1`` ids, the mask's is the last."""
        d = self.cfg["documents"]
        self.tok = make_tokens(self.seed, self.n_seq, self.seq_len, self.dims["vocab_size"] - 1,
                               int(self.cfg["eot_token_id"]), float(d["median_tokens"]),
                               float(d["lognormal_sigma"]), float(d["token_zipf_alpha"]))

    # -- the job ----------------------------------------------------------------
    def _estimator(self, steps: int):
        from flink_ml_tpu.models.lm import DecoderLM

        d = self.dims
        return (
            DecoderLM().set_block_kind("sdar")  # first: a program without the kind refuses here, by name
            .set_num_layers(d["num_hidden_layers"]).set_hidden_size(d["hidden_size"])
            .set_num_heads(d["num_attention_heads"]).set_num_kv_heads(d["num_key_value_heads"])
            .set_head_size(d["head_dim"]).set_rope_theta(float(d["rope_theta"]))
            .set_num_experts(d["num_experts_published"]).set_experts_held(d["num_experts"])
            .set_first_expert_held(d["first_expert_held"]).set_experts_per_token(d["num_experts_per_tok"])
            .set_expert_width(d["moe_intermediate_size"])
            .set_block_length(d["block_length"]).set_mask_token_id(d["mask_token_id"])
            .set_vocab_size(d["vocab_size"]).set_norm_eps(float(d["rms_norm_eps"]))
            .set_compute_type(self.cfg["compute_dtype"])
            .set_max_iter(steps).set_global_batch_size(self.batch)
            .set_learning_rate(self.hyper["learning_rate"]).set_seed(self.seed % (2 ** 31))
        )

    def fit(self) -> dict:
        """One whole fit job; returns host-side numbers only, so that nothing
        pins the fit's device arrays while the next fit allocates its own."""
        d = self.dims
        est = self._estimator(self.steps)
        model = est.fit(self.df)
        del model  # its parameters leave the device here
        loads = np.asarray(est.expert_rows_history)  # [steps, layers, published experts]
        return {
            "losses": list(est.loss_history),
            "grad_norms": list(est.grad_norm_history),
            "group_norms": dict(zip(est.param_names, est.param_grad_norm_history[0])),
            "expert_rows": loads[0],
            "targets_masked": list(est.targets_masked_history),
            "steps_expected": self.steps,
            # both halves of the doubled sequence are routed: 2 T positions a sequence
            "rows_missing": int(self.steps * self.batch * 2 * self.seq_len * d["num_experts_per_tok"]
                                * loads.shape[1] - loads.sum()),
        }

    # -- the output check ---------------------------------------------------------
    def reference(self, precision: str = "f32") -> dict:
        """The head of the job from the same seed, by the plain reference on
        this device: two steps' objectives (each under its own step's draw of
        the corruption), the first step's gradient norms and loads, both steps'
        scored positions. A fit is a function of the seed alone, so the last
        completed fit's first two steps ARE the head of the job the reference
        computes."""
        gc.collect()
        lo2 = self.batch if 2 * self.batch <= self.n_seq else 0
        batches = [self.tok[: self.batch], self.tok[lo2: lo2 + self.batch]]
        out = reference.head_of_job(self.dims, self.hyper, self.seed % (2 ** 31), batches, precision)
        out.update(rows_missing=0, steps_expected=2)
        return out

    def first_update(self) -> dict:
        """The parameters after the job's first step, by name, on the host: a
        fit of ONE step from the same seed (the same jitted step program, its
        first batch and its first draw of the corruption: the head of the job
        the window's fits ran), read back through the model's own data."""
        gc.collect()
        model = self._estimator(1).fit(self.df)
        return model.get_model_data()[0].column("params")[0]

    def held_share(self, rows) -> tuple:
        """Of one step's loads ``[layers, published experts]``: the share of
        the routed rows whose expert is held here, and the fullest held expert
        over the held mean."""
        rows = np.asarray(rows, np.float64)
        lo = self.dims["first_expert_held"]
        held = rows[:, lo: lo + self.dims["num_experts"]]
        return float(held.sum() / rows.sum()), float(held.max() / held.mean())

    def compare(self, got: dict, want: dict) -> dict:
        """The numbers the check holds to their limits: program (or control)
        ``got`` against the float32 reference ``want``.

        ``targets_masked_mismatch`` is exact: the positions each side scored in
        the two steps differ by nothing (the same draws from the same seed: a
        corruption drawn otherwise, a level a batch for a level a sequence, or
        a mask that reached the wrong half shows here before it shows in a
        norm).

        The program's matmul inputs are bfloat16, so a share of the routed rows
        changes expert against the float32 reference, and a layer's router,
        its held experts and the norm before them (``ffn_norm``: its gradient
        reaches it through the held rows and the router alone) move in steps
        with them: those five leaves a layer are held apart from the leaves no
        row's choice moves (JoyAI's split, with the norm: on every one of 23
        sound runs the worst of the others was an ``ffn_norm``, up to 1.0e-1
        where no other leaf passed 5.9e-3). ``group_grad_norm_rel_err`` is the
        worst of the others (the per-head QK-norms' 128 weights among them).
        ``routed_grad_norm_rel_err`` takes each layer's worst of those five
        leaves, and of the layers the MEDIAN: what is wrong in the code a layer runs shows
        in every layer that runs it, a row that changed expert in one.
        ``expert_grad_norm_bias`` is the mean, over the held experts' leaves, of
        the SIGNED relative error of the gradient's norm.
        ``rows_changed_expert_pct`` is the share itself at step 1, as the loads
        bound it from below: a router that did not run in float32 reads a
        multiple of the sound program's.

        ``expert_id_drift`` is what tells a router whose scores lost their
        float32: the mean shift of a routed row's expert id against the float32
        reference, from the loads: ``sum_e (e - 63.5) d_e / routed`` a layer
        with ``d_e`` the change of expert ``e``'s load, the mean of the six
        layers, absolute. Ties among the top-k go to the LOWER id; float32
        scores hardly ever tie, bfloat16 scores (256 values an octave, 128
        experts within three) tie at the eighth place on about one position in
        ten, and every such tie that the float32 order would have given to the
        higher id moves a row DOWN, by a third of the ids on average: a few
        rows off every expert, more gained than lost the lower its id, the same
        way in every layer. Rows that change expert through rounding move
        either way and cancel in the sum. At the init the masked positions of a
        layer hold nearly one vector, so hundreds of rows cross between two
        experts TOGETHER, in the program as in the control: each ``d_e`` is
        capped at three times the layer's median ``|d_e|`` (and one row), which
        leaves the ties' few rows an expert whole and a block two capped terms.

        ``update_rel_err`` compares the first update ITSELF, element by
        element: ``|p1 - p1_ref| / |p1_ref - p0|`` over every parameter (``got``
        is the program: a one-step fit's model; the control: its own updated
        tree). A state left unchanged reads 1, an update twice as long 1, one
        of the wrong sign 2. AdamW's first update is ``lr x sign(g)``, so on a
        sound run the number is twice the root of the share of gradient
        elements whose SIGN differs from the reference's: it holds the
        optimizer's step (rate, decay, sign, every leaf reached) where the
        second step's loss only sees its sum."""
        def rel(a, b):
            return abs(a - b) / abs(b) if b else float(a != 0.0)

        whole = want["grad_norms"][0]
        group = {k: rel(got["group_norms"][k], v) for k, v in want["group_norms"].items()}
        expert_layers = sorted(k.rsplit(".", 1)[0] for k in group if k.endswith(".router"))
        held = [f"{layer}.{leaf}" for layer in expert_layers for leaf in ("w_gate", "w_up", "w_down")]
        routed = {layer: max(group[f"{layer}.{leaf}"] for leaf in ("ffn_norm", "router", "w_gate", "w_up", "w_down"))
                  for layer in expert_layers}
        rest = {k: v for k, v in group.items() if k not in held and not k.endswith((".router", ".ffn_norm"))}
        worst = max(rest, key=rest.get)
        # a held leaf no row reached has no gradient on either side (``rel`` holds it to exactly zero): no part of the mean
        bias = float(np.mean([(got["group_norms"][k] - want["group_norms"][k]) / want["group_norms"][k]
                              for k in held if want["group_norms"][k]] or [0.0]))
        table = sorted(group, key=group.get, reverse=True)[:6]
        print("check_groups " + "; ".join(f"{k} {group[k]:.2e} at {want['group_norms'][k] / whole:.1e} of the whole"
                                          for k in table), flush=True)
        print("check_group_norms " + json.dumps({k: [float(got["group_norms"][k]), v]
                                                 for k, v in want["group_norms"].items()}), flush=True)
        off_loads = np.asarray(got["expert_rows"], np.int64) - want["expert_rows"]  # [layers, published experts]
        moved = np.abs(off_loads).sum(axis=1)
        changed = 100.0 * float(moved.sum()) / 2 / max(1, int(np.sum(want["expert_rows"])))
        cap = 3 * np.median(np.abs(off_loads), axis=1, keepdims=True) + 1
        centred = np.arange(off_loads.shape[1]) - (off_loads.shape[1] - 1) / 2
        drift = (np.clip(off_loads, -cap, cap) * centred).sum(axis=1) / np.maximum(1, want["expert_rows"].sum(axis=1))
        share, fullest = self.held_share(got["expert_rows"])
        scored = list(got["targets_masked"][:2]) + [-1] * 2
        mismatch = sum(abs(int(g) - int(w)) for g, w in zip(scored, want["targets_masked"]))
        print("check_loads " + json.dumps({"got": np.asarray(got["expert_rows"]).tolist(),
                                           "want": want["expert_rows"].tolist()}), flush=True)
        print(f"check_detail objectives {[round(x, 4) for x in got['losses']]} against {want['losses']}; positions "
              f"scored {got['targets_masked'][:2]} against {want['targets_masked']}; "
              f"worst leaf no row's choice moves {worst} {rest[worst]:.3e}; the layers' worst routed leaf "
              f"{[round(float(v), 4) for v in routed.values()]}, "
              f"the {len(held)} held-expert leaves' mean signed {bias:+.3e}; "
              f"routed rows that changed expert at step 1 (lower bound, from the loads): {changed:.4f}%, a layer "
              f"{[int(m) // 2 for m in moved]}, the mean shift of a row's expert id a layer (each expert's change capped) "
              f"{[round(float(x), 4) for x in drift]}; step-1 held share {share:.4f}, fullest held expert over the "
              f"held mean {fullest:.3f}", flush=True)
        after = got.get("params_after") or self.first_update()
        by_kind = {}  # kind of leaf -> [squared distance to the reference's p1, squared length of the reference's step]
        for k, ref in want["params_after"].items():
            sums = by_kind.setdefault(k.rsplit(".", 1)[-1], [0.0, 0.0])
            sums[0] += float(np.sum(np.square(after[k] - ref, dtype=np.float64)))
            sums[1] += want["update_norms"][k] ** 2
        del after  # 2.6 GB of host memory at the cell's size
        print("check_update " + json.dumps({kind: round((n / d) ** 0.5, 5) for kind, (n, d) in by_kind.items()}),
              flush=True)
        loss = [rel(g, w) for g, w in zip(got["losses"], want["losses"])] + [float("inf")] * 2
        return {
            "loss_rel_err": loss[0],
            "loss_after_update_rel_err": loss[1],
            "grad_norm_rel_err": rel(got["grad_norms"][0], want["grad_norms"][0]),
            "group_grad_norm_rel_err": rest[worst],
            "routed_grad_norm_rel_err": float(np.median(list(routed.values()))),
            "expert_grad_norm_bias": abs(bias),
            "rows_changed_expert_pct": changed,
            "expert_id_drift": abs(float(np.mean(drift))),
            "update_rel_err": (sum(n for n, _ in by_kind.values()) / sum(d for _, d in by_kind.values())) ** 0.5,
            "targets_masked_mismatch": float(mismatch),
            "steps_missing": float(got["steps_expected"] - len(got["losses"])),
            "tokens_dropped": float(got["rows_missing"]),
        }


def create(config: dict, seed: int, n_devices: int):
    return SdarLmFit(config, seed, n_devices)
