"""The system under test for the Nemotron-3-Super configuration: the users' own
``models.NemotronHLM`` (Mamba-2 through the Pallas kernels of ``ops.ssd``,
grouped-query attention through the flash kernel, the held relu² experts in
the latent space through the grouped products of ``ops.grouped_matmul``, each
block recomputed in the backward pass) and
``models.train.make_train_step``, built from the configuration's sizes and
handed the benchmark's weights."""

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec

# At import, not in build(): a checkout whose program has no NemotronHLM
# fails here, before a store is written or a weight is made.
from petastorm_tpu.models import NemotronHLM
from petastorm_tpu.models.moe import ExpertLoadCounter
from petastorm_tpu.models.train import TrainState, make_train_step


def model_for(cfg, mesh=None, interpret=False, dtype=jnp.bfloat16):
    a, published = cfg['assumed'], cfg['published']
    if set(cfg['hybrid_override_pattern']) - set('ME*') or len(
            cfg['hybrid_override_pattern']) != cfg['num_hidden_layers']:
        raise ValueError('a block a letter of M, E, *')
    if (cfg['mamba_hidden_act'], cfg['mlp_hidden_act']) != ('silu', 'relu2'):
        raise ValueError('SiLU after the convolution, relu² experts')
    if cfg['n_group'] != 1 or cfg['topk_group'] != 1:
        raise ValueError('RoutedMoE here: plain top-k, no selection by groups')
    if cfg['num_nextn_predict_layers']:
        raise ValueError('no next-token module is built')
    if cfg['mamba_proj_bias'] or cfg['attention_bias'] or cfg['mlp_bias'] \
            or not cfg['use_conv_bias']:
        raise ValueError('no bias but the convolution\'s')
    if cfg['layer_norm_epsilon'] != cfg['norm_eps']:
        raise ValueError('one eps for every norm')
    if published['mamba_num_heads'] * cfg['mamba_head_dim'] \
            != cfg['expand'] * cfg['hidden_size']:
        raise ValueError('the published heads fill expand x hidden')
    if len(a['experts_held']) != cfg['n_routed_experts']:
        raise ValueError('experts_held names the n_routed_experts held')
    return NemotronHLM(
        vocab_size=cfg['vocab_size'], d_model=cfg['hidden_size'],
        pattern=cfg['hybrid_override_pattern'],
        mamba_heads_held=cfg['mamba_num_heads'],
        mamba_heads_published=published['mamba_num_heads'],
        mamba_groups_held=cfg['n_groups'],
        mamba_groups_published=published['n_groups'],
        mamba_head_dim=cfg['mamba_head_dim'], ssm_state=cfg['ssm_state_size'],
        conv_kernel=cfg['conv_kernel'], chunk=cfg['chunk_size'],
        heads_held=cfg['num_attention_heads'],
        heads_published=published['num_attention_heads'],
        kv_heads_held=cfg['num_key_value_heads'],
        kv_heads_published=published['num_key_value_heads'],
        head_dim=cfg['head_dim'],
        experts_published=published['n_routed_experts'],
        experts_held=tuple(a['experts_held']),
        top_k=cfg['num_experts_per_tok'],
        routed_scale=cfg['routed_scaling_factor'],
        expert_d_ff=cfg['moe_intermediate_size'],
        shared_d_ff=cfg['n_shared_experts']
        * cfg['moe_shared_expert_intermediate_size'],
        latent=cfg['moe_latent_size'],
        normalise_top_k=cfg['norm_topk_prob'], eps=cfg['layer_norm_epsilon'],
        attention='flash:interpret' if interpret else 'flash',
        ssm='pallas:interpret' if interpret else 'pallas',
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
