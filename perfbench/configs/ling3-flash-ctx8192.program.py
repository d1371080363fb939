"""The system under test for the Ling-3.0-flash configuration: the users' own
``models.LingHybridLM`` (Kimi delta attention through the Pallas kernels of
``ops.kimi_delta``, the latent-attention layer through the flash kernel with
keys 192 and values 128 wide, the held experts through the grouped products
of ``ops.grouped_matmul`` under group-limited routing, each block recomputed
in the backward pass) and ``models.train.make_train_step``, built from the
configuration's sizes and handed the benchmark's weights."""

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec

# At import, not in build(): a checkout whose program has no LingHybridLM
# fails here, before a store is written or a weight is made.
from petastorm_tpu.models import LingHybridLM
from petastorm_tpu.models.moe import ExpertLoadCounter
from petastorm_tpu.models.train import TrainState, make_train_step


def model_for(cfg, mesh=None, interpret=False, dtype=jnp.bfloat16):
    a, published = cfg['assumed'], cfg['published']
    heads = cfg['num_attention_heads']
    if heads != cfg['num_key_value_heads'] or cfg['q_lora_rank'] is not None:
        raise ValueError('latent attention: a key a head, no query latent')
    if cfg['rope_scaling'] is not None or not cfg['rope_interleave']:
        raise ValueError('plain rotary positions on interleaved pairs')
    if cfg['rms_norm_eps'] != 1e-6:
        raise ValueError('models.hybrid.RMSNorm has eps 1e-6')
    if not (cfg['kda_safe_gate'] and cfg['no_kda_lora'] and cfg['linear_silu']
            and cfg['gated_attention_proj_granularity_type'] == 'head_wise'
            and cfg['group_norm_size'] == 1):
        raise ValueError('KimiDeltaMixer: the bounded gate from a full-rank '
                         'W_f, SiLU after the convolution, one output gate '
                         'and one norm a head')
    if (cfg['scoring_func'], cfg['topk_method']) != ('sigmoid', 'noaux_tc'):
        raise ValueError('RoutedMoE: sigmoid scores, selection by groups')
    if cfg['num_nextn_predict_layers'] or cfg['mtp_loss_scaling_factor']:
        raise ValueError('no next-token module is built')
    if len(a['experts_held']) != cfg['num_experts']:
        raise ValueError('experts_held names the num_experts held')
    # The published layers kept: 0 .. 5 with one leading dense layer dropped.
    dropped = published['first_k_dense_replace'] - cfg['first_k_dense_replace']
    kept = cfg['num_hidden_layers'] + dropped
    if any(cfg['expert_swiglu_limit_list'][:kept]
           + cfg['share_expert_swiglu_limit_list'][:kept]):
        raise ValueError('a kept layer clamps its SwiGLU: not built')
    return LingHybridLM(
        vocab_size=cfg['vocab_size'], d_model=cfg['hidden_size'],
        d_ff=cfg['intermediate_size'], num_layers=cfg['num_hidden_layers'],
        layer_group_size=cfg['layer_group_size'],
        dense_layers=cfg['first_k_dense_replace'], heads_held=heads,
        heads_published=published.get('num_attention_heads', heads),
        key_dim=cfg['head_dim'], value_dim=cfg['head_dim'],
        conv_kernel=cfg['short_conv_kernel_size'],
        gate_lower_bound=cfg['kda_lower_bound'], chunk=a['chunk'],
        sub_block=a['sub_block'], kv_rank=cfg['kv_lora_rank'],
        nope=cfg['qk_nope_head_dim'], rope=cfg['qk_rope_head_dim'],
        v_dim=cfg['v_head_dim'], rope_theta=cfg['rope_theta'],
        experts_published=published['num_experts'],
        experts_held=tuple(a['experts_held']),
        top_k=cfg['num_experts_per_tok'], n_group=cfg['n_group'],
        topk_group=cfg['topk_group'],
        routed_scale=cfg['routed_scaling_factor'],
        expert_d_ff=cfg['moe_intermediate_size'],
        shared_d_ff=cfg['num_shared_experts']
        * cfg['moe_shared_expert_intermediate_size'],
        normalise_top_k=cfg['norm_topk_prob'],
        attention='flash:interpret' if interpret else 'flash',
        linear_attention='pallas:interpret' if interpret else 'pallas',
        experts='pallas:interpret' if interpret else 'pallas',
        expert_tile=a['expert_tile_rows'], remat=a['recompute_each_layer'],
        mesh=mesh, dtype=dtype)


def build(cfg, params, batch_stats, mesh, interpret=False):
    a = cfg['assumed']
    model = model_for(cfg, mesh, interpret)
    tx = optax.adamw(a['learning_rate'], b1=a['b1'], b2=a['b2'], eps=a['eps'],
                     weight_decay=a['weight_decay'])
    state = TrainState.create(apply_fn=model.apply, params=params, tx=tx)
    # The state as the step hands it back (see the GPT-2 program).
    state = state.replace(step=jnp.zeros((), jnp.int32))
    if mesh is not None:
        state = jax.device_put(state, NamedSharding(mesh, PartitionSpec()))
    train_step = make_train_step(mesh=mesh)
    # Next-token prediction: inputs and targets are one row shifted by one.
    prepare = jax.jit(lambda tokens: (tokens[:, :-1], tokens[:, 1:]))
    loads = ExpertLoadCounter()

    def step(state, batch):
        x, y = prepare(batch.tokens)
        state, metrics = train_step(state, x, y)
        loads.add(metrics)
        return state, metrics

    return state, step


def first_gradient(opt_state, cfg):
    """The gradient as the optimizer got it, as a tree and the factor its
    norms take: Adam's first moment after one step from zero is (1 - b1)
    times the gradient."""
    return opt_state[0].mu, 1.0 / (1.0 - cfg['assumed']['b1'])
