"""The parameter tree of every kind of stack is what it was before one
description of a layer (``config.layers``) took over from a leaves function a
kind: leaf for leaf the names, shapes, init kinds and - the initialiser numbers
leaves by position - the ORDER that 512ebfa (PR 42) gave. A moved leaf is
another model from the same seed. Toys by literal; the benchmark's five LM
configurations by the digest of the list and by their parameter counts. The
``joyai`` kind (PR 44) is pinned beside them as it came: its stack's leaves,
then ``final_norm`` and ``lm_head``, then the multi-token-prediction module;
the ``sdar`` kind (PR 47) likewise: OLMoE's order, its QK-norms a head wide;
the ``solar_open2`` kind (PR 51) as it came: an attention layer's leaves with
``wg`` before ``wo``, a delta-rule layer's fifteen, laguna's expert leaves;
the ``olmo_hybrid`` kind (PR 54) as it came: no norm before a sublayer, a
one-decay delta-rule layer's thirteen leaves and then ``attn_out_norm``, the
dense SwiGLU's three and ``ffn_out_norm``."""
import hashlib

import pytest

from flink_ml_tpu.models.lm.config import LMConfig, layers, num_params, param_shapes
from tests.test_lm_chip_compile import (
    _cell_config, _joyai_cut, _laguna_cut, _nemotron_cut, _olmo_hybrid_cut, _ouro_cut, _sdar_cut, _solar_cut, _zaya_cut,
)

TOYS = {
    "olmoe": LMConfig(n_layers=1, hidden=128, n_heads=4, n_experts=8, top_k=2, expert_width=64, vocab=512),
    "zaya": LMConfig(n_layers=2, hidden=128, n_heads=4, n_experts=8, top_k=1, expert_width=64, vocab=512,
                     rope_theta=5e6, aux_coef=0.0, block="zaya", tied=True, experts_held=4, first_held=2,
                     n_kv_heads=2, head_size=16, rope_fraction=0.5, router_width=32),
    "ouro": LMConfig(n_layers=1, hidden=128, n_heads=4, n_experts=0, top_k=0, expert_width=192, vocab=512,
                     rope_theta=1e6, norm_eps=1e-6, aux_coef=0.0, block="ouro", loops=3, exit_beta=0.1),
    "laguna": LMConfig(n_layers=2, hidden=128, n_heads=4, n_experts=16, top_k=2, expert_width=64, vocab=512,
                       aux_coef=0.0, block="laguna", experts_held=2, first_held=2, n_kv_heads=2, head_size=16,
                       rope_fraction=0.5, layer_heads=(4, 8), layer_windows=(0, 96), n_dense=1, dense_width=96,
                       shared_width=32, routed_scale=2.5, window_rope_theta=1e4),
    "nemotron_h": LMConfig(n_layers=3, hidden=64, n_heads=4, n_experts=16, top_k=2, expert_width=32, vocab=512,
                           norm_eps=1e-5, aux_coef=0.0, block="nemotron_h", experts_held=2, first_held=2,
                           n_kv_heads=2, head_size=16, shared_width=48, routed_scale=2.5,
                           layer_kinds=tuple("M*E"), ssm_heads=8, ssm_head_dim=8, ssm_groups=2, ssm_state=16,
                           conv_kernel=4, chunk=64),
    "joyai": LMConfig(n_layers=2, hidden=64, n_heads=4, n_experts=16, top_k=2, expert_width=32, vocab=512,
                      rope_theta=3.2e7, norm_eps=1e-6, aux_coef=0.0, block="joyai", experts_held=2, first_held=2,
                      n_dense=1, dense_width=96, shared_width=32, routed_scale=2.5, q_rank=48, kv_rank=32, nope_dim=16,
                      rope_dim=8, v_dim=12, mtp_depth=1, mtp_coef=0.3),
    "sdar": LMConfig(n_layers=1, hidden=64, n_heads=4, n_experts=16, top_k=2, expert_width=32, vocab=512,
                     rope_theta=1e6, norm_eps=1e-6, aux_coef=0.0, block="sdar", experts_held=2, first_held=2,
                     n_kv_heads=2, head_size=16, block_length=4, mask_id=511),
    "solar_open2": LMConfig(n_layers=2, hidden=64, n_heads=4, n_experts=16, top_k=2, expert_width=32, vocab=512,
                            norm_eps=1e-5, aux_coef=0.0, block="solar_open2", experts_held=2, first_held=2,
                            n_kv_heads=2, head_size=16, shared_width=32, routed_scale=1.0, conv_kernel=4, chunk=64,
                            gqa_layers=(0,), kda_heads=2, kda_head_dim=16),
    "olmo_hybrid": LMConfig(n_layers=2, hidden=64, n_heads=4, n_experts=0, top_k=0, expert_width=96, vocab=512,
                            norm_eps=1e-6, aux_coef=0.0, block="olmo_hybrid", n_kv_heads=4, head_size=16, conv_kernel=4,
                            chunk=64, gqa_layers=(1,), kda_heads=3, kda_head_dim=8, kda_value_dim=16),
}
#: ``(dotted path, shape, init)`` of every leaf in ``param_shapes`` order, printed by 512ebfa's ``param_shapes``
TOY_TREES = {
    "olmoe": [
        ('embed', (512, 128), 'normal'), ('layers.0.attn_norm', (128,), 'ones'),
        ('layers.0.wq', (128, 128), 'normal'), ('layers.0.wk', (128, 128), 'normal'),
        ('layers.0.wv', (128, 128), 'normal'), ('layers.0.wo', (128, 128), 'normal'),
        ('layers.0.q_norm', (128,), 'ones'), ('layers.0.k_norm', (128,), 'ones'),
        ('layers.0.ffn_norm', (128,), 'ones'), ('layers.0.router', (128, 8), 'normal'),
        ('layers.0.w_gate', (8, 128, 64), 'normal'), ('layers.0.w_up', (8, 128, 64), 'normal'),
        ('layers.0.w_down', (8, 64, 128), 'normal'), ('final_norm', (128,), 'ones'),
        ('lm_head', (128, 512), 'normal'),
    ],
    "zaya": [
        ('embed', (512, 128), 'normal'), ('layers.0.attn_norm', (128,), 'ones'),
        ('layers.0.attn_res_scale', (128,), 'ones'), ('layers.0.attn_res_bias', (128,), 'zeros'),
        ('layers.0.attn_out_scale', (128,), 'ones'), ('layers.0.attn_out_bias', (128,), 'zeros'),
        ('layers.0.wq', (128, 64), 'normal'), ('layers.0.wk', (128, 32), 'normal'),
        ('layers.0.wv1', (128, 16), 'normal'), ('layers.0.wv2', (128, 16), 'normal'),
        ('layers.0.conv0_w', (2, 96), 'normal'), ('layers.0.conv0_b', (96,), 'zeros'),
        ('layers.0.conv1_w', (2, 6, 16, 16), 'normal'), ('layers.0.conv1_b', (6, 16), 'zeros'),
        ('layers.0.k_temp', (2,), 'ones'), ('layers.0.wo', (64, 128), 'small'), ('layers.0.ffn_norm', (128,), 'ones'),
        ('layers.0.ffn_res_scale', (128,), 'ones'), ('layers.0.ffn_res_bias', (128,), 'zeros'),
        ('layers.0.ffn_out_scale', (128,), 'ones'), ('layers.0.ffn_out_bias', (128,), 'zeros'),
        ('layers.0.router_in', (128, 32), 'normal'), ('layers.0.router_norm', (32,), 'ones'),
        ('layers.0.router_w1', (32, 32), 'normal'), ('layers.0.router_w2', (32, 32), 'normal'),
        ('layers.0.router_w3', (32, 8), 'normal'), ('layers.0.w_gate', (4, 128, 64), 'normal'),
        ('layers.0.w_up', (4, 128, 64), 'normal'), ('layers.0.w_down', (4, 64, 128), 'normal'),
        ('layers.1.attn_norm', (128,), 'ones'), ('layers.1.attn_res_scale', (128,), 'ones'),
        ('layers.1.attn_res_bias', (128,), 'zeros'), ('layers.1.attn_out_scale', (128,), 'ones'),
        ('layers.1.attn_out_bias', (128,), 'zeros'), ('layers.1.wq', (128, 64), 'normal'),
        ('layers.1.wk', (128, 32), 'normal'), ('layers.1.wv1', (128, 16), 'normal'),
        ('layers.1.wv2', (128, 16), 'normal'), ('layers.1.conv0_w', (2, 96), 'normal'),
        ('layers.1.conv0_b', (96,), 'zeros'), ('layers.1.conv1_w', (2, 6, 16, 16), 'normal'),
        ('layers.1.conv1_b', (6, 16), 'zeros'), ('layers.1.k_temp', (2,), 'ones'),
        ('layers.1.wo', (64, 128), 'small'), ('layers.1.ffn_norm', (128,), 'ones'),
        ('layers.1.ffn_res_scale', (128,), 'ones'), ('layers.1.ffn_res_bias', (128,), 'zeros'),
        ('layers.1.ffn_out_scale', (128,), 'ones'), ('layers.1.ffn_out_bias', (128,), 'zeros'),
        ('layers.1.router_in', (128, 32), 'normal'), ('layers.1.router_gamma', (32,), 'zeros'),
        ('layers.1.router_norm', (32,), 'ones'), ('layers.1.router_w1', (32, 32), 'normal'),
        ('layers.1.router_w2', (32, 32), 'normal'), ('layers.1.router_w3', (32, 8), 'normal'),
        ('layers.1.w_gate', (4, 128, 64), 'normal'), ('layers.1.w_up', (4, 128, 64), 'normal'),
        ('layers.1.w_down', (4, 64, 128), 'normal'), ('final_norm', (128,), 'ones'),
    ],
    "ouro": [
        ('embed', (512, 128), 'normal'), ('layers.0.attn_norm', (128,), 'ones'),
        ('layers.0.wq', (128, 128), 'normal'), ('layers.0.wk', (128, 128), 'normal'),
        ('layers.0.wv', (128, 128), 'normal'), ('layers.0.wo', (128, 128), 'normal'),
        ('layers.0.attn_out_norm', (128,), 'ones'), ('layers.0.ffn_norm', (128,), 'ones'),
        ('layers.0.w_gate', (128, 192), 'normal'), ('layers.0.w_up', (128, 192), 'normal'),
        ('layers.0.w_down', (192, 128), 'normal'), ('layers.0.ffn_out_norm', (128,), 'ones'),
        ('final_norm', (128,), 'ones'), ('lm_head', (128, 512), 'normal'), ('exit_gate_w', (128, 1), 'normal'),
        ('exit_gate_b', (1,), 'zeros'),
    ],
    "laguna": [
        ('embed', (512, 128), 'normal'), ('layers.0.attn_norm', (128,), 'ones'), ('layers.0.wq', (128, 64), 'normal'),
        ('layers.0.wk', (128, 32), 'normal'), ('layers.0.wv', (128, 32), 'normal'),
        ('layers.0.head_gate', (128, 4), 'normal'), ('layers.0.wo', (64, 128), 'normal'),
        ('layers.0.ffn_norm', (128,), 'ones'), ('layers.0.w_gate', (128, 96), 'normal'),
        ('layers.0.w_up', (128, 96), 'normal'), ('layers.0.w_down', (96, 128), 'normal'),
        ('layers.1.attn_norm', (128,), 'ones'), ('layers.1.wq', (128, 128), 'normal'),
        ('layers.1.wk', (128, 32), 'normal'), ('layers.1.wv', (128, 32), 'normal'),
        ('layers.1.head_gate', (128, 8), 'normal'), ('layers.1.wo', (128, 128), 'normal'),
        ('layers.1.ffn_norm', (128,), 'ones'), ('layers.1.router', (128, 16), 'normal'),
        ('layers.1.router_bias', (16,), 'zeros'), ('layers.1.shared_gate', (128, 32), 'normal'),
        ('layers.1.shared_up', (128, 32), 'normal'), ('layers.1.shared_down', (32, 128), 'normal'),
        ('layers.1.w_gate', (2, 128, 64), 'normal'), ('layers.1.w_up', (2, 128, 64), 'normal'),
        ('layers.1.w_down', (2, 64, 128), 'normal'), ('final_norm', (128,), 'ones'),
        ('lm_head', (128, 512), 'normal'),
    ],
    "nemotron_h": [
        ('embed', (512, 64), 'normal'), ('layers.0.norm', (64,), 'ones'), ('layers.0.in_proj', (64, 200), 'normal'),
        ('layers.0.conv_w', (4, 128), 'normal'), ('layers.0.conv_b', (128,), 'zeros'),
        ('layers.0.dt_bias', (8,), 'dt_bias'), ('layers.0.A_log', (8,), 'a_log'), ('layers.0.D', (8,), 'ones'),
        ('layers.0.gate_norm', (64,), 'ones'), ('layers.0.out_proj', (64, 64), 'normal'),
        ('layers.1.norm', (64,), 'ones'), ('layers.1.wq', (64, 64), 'normal'), ('layers.1.wk', (64, 32), 'normal'),
        ('layers.1.wv', (64, 32), 'normal'), ('layers.1.wo', (64, 64), 'normal'), ('layers.2.norm', (64,), 'ones'),
        ('layers.2.router', (64, 16), 'normal'), ('layers.2.router_bias', (16,), 'zeros'),
        ('layers.2.shared_up', (64, 48), 'normal'), ('layers.2.shared_down', (48, 64), 'normal'),
        ('layers.2.w_up', (2, 64, 32), 'normal'), ('layers.2.w_down', (2, 32, 64), 'normal'),
        ('final_norm', (64,), 'ones'), ('lm_head', (64, 512), 'normal'),
    ],
    "joyai": [
        ('embed', (512, 64), 'normal'), ('layers.0.attn_norm', (64,), 'ones'), ('layers.0.wq_a', (64, 48), 'normal'),
        ('layers.0.q_a_norm', (48,), 'ones'), ('layers.0.wq_b', (48, 96), 'normal'),
        ('layers.0.wkv_a', (64, 40), 'normal'), ('layers.0.kv_a_norm', (32,), 'ones'),
        ('layers.0.wkv_b', (32, 112), 'normal'), ('layers.0.wo', (48, 64), 'normal'),
        ('layers.0.ffn_norm', (64,), 'ones'), ('layers.0.w_gate', (64, 96), 'normal'),
        ('layers.0.w_up', (64, 96), 'normal'), ('layers.0.w_down', (96, 64), 'normal'),
        ('layers.1.attn_norm', (64,), 'ones'), ('layers.1.wq_a', (64, 48), 'normal'),
        ('layers.1.q_a_norm', (48,), 'ones'), ('layers.1.wq_b', (48, 96), 'normal'),
        ('layers.1.wkv_a', (64, 40), 'normal'), ('layers.1.kv_a_norm', (32,), 'ones'),
        ('layers.1.wkv_b', (32, 112), 'normal'), ('layers.1.wo', (48, 64), 'normal'),
        ('layers.1.ffn_norm', (64,), 'ones'), ('layers.1.router', (64, 16), 'normal'),
        ('layers.1.router_bias', (16,), 'zeros'), ('layers.1.shared_gate', (64, 32), 'normal'),
        ('layers.1.shared_up', (64, 32), 'normal'), ('layers.1.shared_down', (32, 64), 'normal'),
        ('layers.1.w_gate', (2, 64, 32), 'normal'), ('layers.1.w_up', (2, 64, 32), 'normal'),
        ('layers.1.w_down', (2, 32, 64), 'normal'), ('final_norm', (64,), 'ones'), ('lm_head', (64, 512), 'normal'),
        ('mtp.enorm', (64,), 'ones'), ('mtp.hnorm', (64,), 'ones'), ('mtp.eh_proj', (128, 64), 'normal'),
        ('mtp.layer.attn_norm', (64,), 'ones'), ('mtp.layer.wq_a', (64, 48), 'normal'),
        ('mtp.layer.q_a_norm', (48,), 'ones'), ('mtp.layer.wq_b', (48, 96), 'normal'),
        ('mtp.layer.wkv_a', (64, 40), 'normal'), ('mtp.layer.kv_a_norm', (32,), 'ones'),
        ('mtp.layer.wkv_b', (32, 112), 'normal'), ('mtp.layer.wo', (48, 64), 'normal'),
        ('mtp.layer.ffn_norm', (64,), 'ones'), ('mtp.layer.router', (64, 16), 'normal'),
        ('mtp.layer.router_bias', (16,), 'zeros'), ('mtp.layer.shared_gate', (64, 32), 'normal'),
        ('mtp.layer.shared_up', (64, 32), 'normal'), ('mtp.layer.shared_down', (32, 64), 'normal'),
        ('mtp.layer.w_gate', (2, 64, 32), 'normal'), ('mtp.layer.w_up', (2, 64, 32), 'normal'),
        ('mtp.layer.w_down', (2, 32, 64), 'normal'), ('mtp.norm', (64,), 'ones'),
    ],
    "sdar": [
        ('embed', (512, 64), 'normal'), ('layers.0.attn_norm', (64,), 'ones'), ('layers.0.wq', (64, 64), 'normal'),
        ('layers.0.wk', (64, 32), 'normal'), ('layers.0.wv', (64, 32), 'normal'), ('layers.0.wo', (64, 64), 'normal'),
        ('layers.0.q_norm', (16,), 'ones'), ('layers.0.k_norm', (16,), 'ones'), ('layers.0.ffn_norm', (64,), 'ones'),
        ('layers.0.router', (64, 16), 'normal'), ('layers.0.w_gate', (2, 64, 32), 'normal'),
        ('layers.0.w_up', (2, 64, 32), 'normal'), ('layers.0.w_down', (2, 32, 64), 'normal'),
        ('final_norm', (64,), 'ones'), ('lm_head', (64, 512), 'normal'),
    ],
    "solar_open2": [
        ('embed', (512, 64), 'normal'), ('layers.0.attn_norm', (64,), 'ones'), ('layers.0.wq', (64, 64), 'normal'),
        ('layers.0.wk', (64, 32), 'normal'), ('layers.0.wv', (64, 32), 'normal'), ('layers.0.wg', (64, 64), 'normal'),
        ('layers.0.wo', (64, 64), 'normal'),
        ('layers.0.ffn_norm', (64,), 'ones'), ('layers.0.router', (64, 16), 'normal'),
        ('layers.0.router_bias', (16,), 'zeros'), ('layers.0.shared_gate', (64, 32), 'normal'),
        ('layers.0.shared_up', (64, 32), 'normal'), ('layers.0.shared_down', (32, 64), 'normal'),
        ('layers.0.w_gate', (2, 64, 32), 'normal'), ('layers.0.w_up', (2, 64, 32), 'normal'),
        ('layers.0.w_down', (2, 32, 64), 'normal'),
        ('layers.1.attn_norm', (64,), 'ones'), ('layers.1.wq', (64, 32), 'normal'),
        ('layers.1.wk', (64, 32), 'normal'), ('layers.1.wv', (64, 32), 'normal'),
        ('layers.1.conv_q', (4, 32), 'normal'), ('layers.1.conv_k', (4, 32), 'normal'),
        ('layers.1.conv_v', (4, 32), 'normal'), ('layers.1.Fa', (64, 16), 'normal'),
        ('layers.1.Fb', (16, 32), 'normal'), ('layers.1.A_log', (2,), 'a_log'), ('layers.1.dt_bias', (32,), 'dt_bias'),
        ('layers.1.Wb', (64, 2), 'normal'), ('layers.1.Ga', (64, 16), 'normal'), ('layers.1.Gb', (16, 32), 'normal'),
        ('layers.1.o_norm', (16,), 'ones'), ('layers.1.wo', (32, 64), 'normal'),
        ('layers.1.ffn_norm', (64,), 'ones'), ('layers.1.router', (64, 16), 'normal'),
        ('layers.1.router_bias', (16,), 'zeros'), ('layers.1.shared_gate', (64, 32), 'normal'),
        ('layers.1.shared_up', (64, 32), 'normal'), ('layers.1.shared_down', (32, 64), 'normal'),
        ('layers.1.w_gate', (2, 64, 32), 'normal'), ('layers.1.w_up', (2, 64, 32), 'normal'),
        ('layers.1.w_down', (2, 32, 64), 'normal'),
        ('final_norm', (64,), 'ones'), ('lm_head', (64, 512), 'normal'),
    ],
    "olmo_hybrid": [
        ('embed', (512, 64), 'normal'), ('layers.0.wq', (64, 24), 'normal'), ('layers.0.wk', (64, 24), 'normal'),
        ('layers.0.wv', (64, 48), 'normal'), ('layers.0.conv_q', (4, 24), 'normal'),
        ('layers.0.conv_k', (4, 24), 'normal'), ('layers.0.conv_v', (4, 48), 'normal'),
        ('layers.0.Wa', (64, 3), 'normal'), ('layers.0.A_log', (3,), 'a_log'), ('layers.0.dt_bias', (3,), 'dt_bias'),
        ('layers.0.Wb', (64, 3), 'normal'), ('layers.0.wg', (64, 48), 'normal'), ('layers.0.o_norm', (16,), 'ones'),
        ('layers.0.wo', (48, 64), 'normal'), ('layers.0.attn_out_norm', (64,), 'ones'),
        ('layers.0.w_gate', (64, 96), 'normal'), ('layers.0.w_up', (64, 96), 'normal'),
        ('layers.0.w_down', (96, 64), 'normal'), ('layers.0.ffn_out_norm', (64,), 'ones'),
        ('layers.1.wq', (64, 64), 'normal'), ('layers.1.wk', (64, 64), 'normal'),
        ('layers.1.wv', (64, 64), 'normal'), ('layers.1.wo', (64, 64), 'normal'), ('layers.1.q_norm', (64,), 'ones'),
        ('layers.1.k_norm', (64,), 'ones'), ('layers.1.attn_out_norm', (64,), 'ones'),
        ('layers.1.w_gate', (64, 96), 'normal'), ('layers.1.w_up', (64, 96), 'normal'),
        ('layers.1.w_down', (96, 64), 'normal'), ('layers.1.ffn_out_norm', (64,), 'ones'),
        ('final_norm', (64,), 'ones'), ('lm_head', (64, 512), 'normal'),
    ],
}


def _olmoe_cut():
    c = _cell_config("olmoe_1b_7b")
    return c, LMConfig(c["num_hidden_layers"], c["hidden_size"], c["num_attention_heads"], c["num_experts"],
                       c["num_experts_per_tok"], c["intermediate_size"], c["vocab_size"],
                       rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
                       aux_coef=float(c["router_aux_loss_coef"]))


#: configuration -> (its LMConfig, parameters, sha256 of ``repr([(path, shape, init), ...])`` at 512ebfa, the distinct
#: layer records of its stack: the sub-programs its layers trace to)
CELLS = {
    "olmoe_1b_7b": (_olmoe_cut, 625_616_896, "68c25eeb25e0299c26be64e1390065259965f17e806cca3857e016485d28d506", 1),
    "zaya1_8b": (_zaya_cut, 708_659_980, "eb5116fe0aa4da41f3fddbb0d8cd04f59bfe98abb0370065c7ee318e70dfae48", 2),
    "ouro_2_6b": (_ouro_cut, 509_661_185, "65bfb1ff3c1c53c800669bc9a0dfcd5ec56c5c43e2d66d7b68108253f759d92d", 1),
    "laguna_xs2": (_laguna_cut, 691_624_960, "19afbae2c071718b1b34260a15131e6907f4cae9e28a3561119774cbf244fc01", 3),
    "nemotron3_nano_30b": (_nemotron_cut, 666_963_456,
                           "f41d67cb520ef871addd10ae18b186dcc768f777eb3c201e86c700ff33bd1c08", 3),
    # as PR 44 brought it: the stack's two records, the module's layer being the second again
    "joyai_llm_flash": (_joyai_cut, 680_441_088, "4d26d90bc1061ad9243f991a5a9928089e0e0c91df5c30ca033534a38a14b211", 2),
    # as PR 47 brought it: six layers of one record
    "sdar_30b_a3b": (_sdar_cut, 645_623_296, "2c858655427e58fe0af64a14d0340659d780d9481f3c4b732df96af708c7e554", 1),
    # as PR 51 brought it: the layer that attends, then three delta-rule layers of one record
    "solar_open2_250b": (_solar_cut, 840_872_600, "9dcffb5157ddd100e16d0d5f3cef8878906ec50434592e42479b60df07681406", 2),
    # as PR 54 brought it: three one-decay delta-rule layers of one record, then the layer that attends
    "olmo_hybrid_7b": (_olmo_hybrid_cut, 766_241_946,
                       "9fb96f77b0d6f00880b3c9951f5d5067d666e5e90620db86bd938fa6e2b212cd", 2),
}


@pytest.mark.parametrize("kind", sorted(TOYS))
def test_a_toy_stacks_tree_is_the_parents_leaf_for_leaf(kind):
    got = [(".".join(str(key) for key in path), shape, init) for path, shape, init in param_shapes(TOYS[kind])]
    assert got == TOY_TREES[kind]


@pytest.mark.parametrize("name", sorted(CELLS))
def test_a_cells_tree_is_the_parents_leaf_for_leaf(name):
    cut, count, digest, distinct = CELLS[name]
    _, cfg = cut()
    tree = [(path, shape, init) for path, shape, init in param_shapes(cfg)]
    assert num_params(cfg) == count
    assert hashlib.sha256(repr(tree).encode()).hexdigest() == digest
    assert len(layers(cfg)) == cfg.n_layers and len(set(layers(cfg))) == distinct
