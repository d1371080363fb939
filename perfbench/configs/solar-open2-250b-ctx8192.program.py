"""The system under test for the Solar-Open2-250B configuration: the users' own
``models.LingHybridLM`` laid out by the configuration's ``gqa_layers``
(Kimi delta attention with the unbounded decay through the exact path of the
Pallas kernels of ``ops.kimi_delta``, gated grouped-query attention with no
positions through the flash kernel, the held experts through the grouped
products of ``ops.grouped_matmul`` under plain top-8 routing, each block
recomputed in the backward pass) and ``models.train.make_train_step``, built
from the configuration's sizes and handed the benchmark's weights."""

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec

# At import, not in build(): a checkout whose LingHybridLM has no layer
# pattern or unbounded decay fails here, before a store is written or a
# weight is made.
from petastorm_tpu.models import LingHybridLM
from petastorm_tpu.models.ling_hybrid import DECAYS  # noqa: F401
from petastorm_tpu.models.moe import ExpertLoadCounter
from petastorm_tpu.models.train import TrainState, make_train_step


def model_for(cfg, mesh=None, interpret=False, dtype=jnp.bfloat16):
    a, published = cfg['assumed'], cfg['published']
    lin = cfg['linear_attn_config']
    heads = cfg['num_attention_heads']
    if lin['num_heads'] != heads or lin['head_dim'] != cfg['head_dim']:
        raise ValueError('one head count and width held in both mixers')
    if cfg['use_rope'] or not cfg['use_gqa_gate']:
        raise ValueError('grouped-query attention with no positions and a '
                         'gate')
    if cfg['kda_use_full_proj'] or not cfg['kda_allow_neg_eigval']:
        raise ValueError('KimiDeltaMixer: low-rank decay and gate, write '
                         'strengths in (0, 2)')
    if cfg['first_k_dense_replace'] or cfg['n_shared_experts'] != 1:
        raise ValueError('experts in every layer, one shared expert')
    if len(a['experts_held']) != cfg['n_routed_experts']:
        raise ValueError('experts_held names the n_routed_experts held')
    return LingHybridLM(
        vocab_size=cfg['vocab_size'], d_model=cfg['hidden_size'],
        d_ff=cfg['intermediate_size'], num_layers=cfg['num_hidden_layers'],
        layer_pattern=tuple('gqa' if i in cfg['gqa_layers'] else 'kda'
                            for i in range(cfg['num_hidden_layers'])),
        dense_layers=0, heads_held=heads,
        heads_published=published['num_attention_heads'],
        kv_heads_held=cfg['num_key_value_heads'],
        kv_heads_published=published['num_key_value_heads'],
        key_dim=lin['head_dim'], value_dim=lin['head_dim'],
        conv_kernel=lin['short_conv_kernel_size'], decay='softplus',
        low_rank=a['low_rank'], gate='channel', beta_scale=2.0,
        eps=cfg['rms_norm_eps'], chunk=a['chunk'], sub_block=a['sub_block'],
        experts_published=published['n_routed_experts'],
        experts_held=tuple(a['experts_held']),
        top_k=cfg['num_experts_per_tok'], n_group=1, topk_group=1,
        routed_scale=cfg['routed_scaling_factor'],
        expert_d_ff=cfg['moe_intermediate_size'],
        shared_d_ff=cfg['n_shared_experts'] * cfg['moe_intermediate_size'],
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
    counters = ExpertLoadCounter()

    def step(state, batch):
        x, y = prepare(batch.tokens)
        state, metrics = train_step(state, x, y)
        counters.add(metrics)
        return state, metrics

    return state, step


def first_gradient(opt_state, cfg):
    """The gradient as the optimizer got it, as a tree and the factor its
    norms take: Adam's first moment after one step from zero is (1 - b1)
    times the gradient."""
    return opt_state[0].mu, 1.0 / (1.0 - cfg['assumed']['b1'])
