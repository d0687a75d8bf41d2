"""``DecoderLM``: OLMoE's block (QK-norm attention on the fused fold, dropless
top-k SwiGLU experts) trained through ``Estimator.fit`` at toy size, then
scored with ``transform`` (per-row mean next-token log-likelihood).
"""
import numpy as np

from flink_ml_tpu.api.dataframe import DataFrame
from flink_ml_tpu.models.lm import DecoderLM


def main():
    rng = np.random.default_rng(0)
    vocab, length = 64, 256
    # a language with structure to learn: each token follows its predecessor + 1
    start = rng.integers(0, vocab, (8, 1))
    tokens = (start + np.arange(length)[None, :]) % vocab
    df = DataFrame.from_dict({"features": tokens})

    lm = (
        DecoderLM()
        .set_num_layers(1).set_hidden_size(64).set_num_heads(2)
        .set_num_experts(4).set_experts_per_token(2).set_expert_width(32)
        .set_vocab_size(vocab)
        .set_max_iter(12).set_global_batch_size(4).set_learning_rate(3e-3).set_seed(0)
    )
    model = lm.fit(df)
    print("loss:", " ".join(f"{x:.3f}" for x in lm.loss_history))
    print("rows per expert at the last step:", lm.expert_rows_history[-1][0].tolist())
    scored = model.transform(df)
    print("mean log-likelihood per row:", np.round(scored.scalars("prediction")[:4], 3).tolist())


if __name__ == "__main__":
    main()
