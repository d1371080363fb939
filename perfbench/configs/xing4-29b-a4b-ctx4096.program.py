"""The system under test for the Xing4.0-29B-A4B configuration: the users' own
``models.LatentMoELM`` (latent attention through the flash kernel with keys
192 and values 128 wide, the held experts through the grouped products of
``ops.grouped_matmul``, four residual streams, the second-next-token module,
each block recomputed in the backward pass) and
``models.train.make_train_step``, built from the configuration's sizes and
handed the benchmark's weights."""

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec

# At import, not in build(): a checkout whose program has no LatentMoELM fails
# here, before a store is written or a weight is made.
from petastorm_tpu.models import LatentMoELM
from petastorm_tpu.models.moe import ExpertLoadCounter
from petastorm_tpu.models.train import TrainState, make_train_step


def model_for(cfg, mesh=None, interpret=False, dtype=jnp.bfloat16):
    a, published, rope = cfg['assumed'], cfg['published'], cfg['rope_scaling']
    if cfg['num_attention_heads'] != cfg['num_key_value_heads']:
        raise ValueError('latent attention holds a key a head')
    if not (cfg['rms_norm_eps'] == cfg['hc_eps'] == 1e-6):
        raise ValueError('models.hybrid.RMSNorm has eps 1e-6')
    if rope['type'] != 'yarn' or rope['mscale'] != rope['mscale_all_dim']:
        raise ValueError('yarn with mscale == mscale_all_dim is what '
                         'LatentMoELM scales its scores for')
    if (cfg['n_group'], cfg['topk_group'], cfg['scoring_func']) != (
            1, 1, 'sigmoid'):
        raise ValueError('RoutedMoE: sigmoid scores, no groups')
    if cfg['num_nextn_predict_layers'] != 1:
        raise ValueError('a row of T + 1 tokens feeds one next-token module')
    if len(a['experts_held']) != cfg['n_routed_experts']:
        raise ValueError('experts_held names the n_routed_experts held')
    return LatentMoELM(
        vocab_size=cfg['vocab_size'], d_model=cfg['hidden_size'],
        d_ff=cfg['intermediate_size'], num_layers=cfg['num_hidden_layers'],
        dense_layers=cfg['first_k_dense_replace'],
        heads_held=cfg['num_attention_heads'],
        heads_published=published['num_attention_heads'],
        q_rank=cfg['q_lora_rank'], kv_rank=cfg['kv_lora_rank'],
        nope=cfg['qk_nope_head_dim'], rope=cfg['qk_rope_head_dim'],
        v_dim=cfg['v_head_dim'], rope_theta=cfg['rope_theta'],
        rope_factor=rope['factor'],
        rope_original_length=rope['original_max_position_embeddings'],
        rope_beta_fast=rope['beta_fast'], rope_beta_slow=rope['beta_slow'],
        rope_mscale_all_dim=rope['mscale_all_dim'],
        experts_published=published['n_routed_experts'],
        experts_held=tuple(a['experts_held']),
        top_k=cfg['num_experts_per_tok'],
        routed_scale=cfg['routed_scaling_factor'],
        expert_d_ff=cfg['moe_intermediate_size'],
        shared_experts=cfg['n_shared_experts'],
        normalise_top_k=cfg['norm_topk_prob'], streams=cfg['hc_mult'],
        sinkhorn_iterations=cfg['hc_sinkhorn_iters'],
        stream_eps=cfg['hc_eps'],
        stream_clamp=(cfg['mhc_h_res_clamp_min'], cfg['mhc_h_res_clamp_max']),
        stream_alpha_init=a['hc_alpha_init'],
        stream_res_diagonal_init=a['hc_res_diagonal_init'],
        nextn=cfg['num_nextn_predict_layers'],
        attention='flash:interpret' if interpret else 'flash',
        experts='pallas:interpret' if interpret else 'pallas',
        expert_tile=a['expert_tile_rows'],
        remat=a['recompute_each_layer'], mesh=mesh, dtype=dtype)


def targets_for(tokens, cfg):
    """The heads' ``(labels, weights)`` from rows of ``T + 1`` tokens: head
    ``k`` (0 the next token) is held to token ``i + k + 1`` at position
    ``i``; its weights make the mean over the ``T - k`` positions that have
    such a token in the row, times ``mtp_loss_weight`` beyond the first."""
    rows, t = tokens.shape[0], tokens.shape[1] - 1
    out = []
    for k in range(cfg['num_nextn_predict_layers'] + 1):
        labels = jnp.pad(tokens[:, k + 1:], ((0, 0), (0, k)))
        weight = (cfg['assumed']['mtp_loss_weight'] if k else 1.0) \
            / (rows * (t - k))
        weights = jnp.where(jnp.arange(t) < t - k, weight, 0.0)
        out.append((labels, jnp.broadcast_to(
            weights.astype(jnp.float32), (rows, t))))
    return tuple(out)


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
    # A row of T + 1 tokens is what the model reads with one next-token
    # module (position i sees token i, the module token i + 1 besides), and
    # both heads' targets come from the same row.
    prepare = jax.jit(lambda tokens: (tokens, targets_for(tokens, cfg)))
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
