"""Sharded training step: jit over a Mesh with dp (data) + tp (model) axes.

The input pipeline delivers batches already laid out on the mesh
(``jax_loader``), so the train step is a pure pjit program: parameters are
replicated over 'data' and (for the wide classifier head) sharded over
'model'; XLA inserts the gradient all-reduce over ICI from the sharding
annotations — no hand-rolled collectives (SURVEY.md §5.8).

Loss and accuracy come from one op, ``ops.cross_entropy``: the models hand
back float32 logits, and inside the jitted step that cast fuses into the
op's reads, so what lies in HBM is the logits as the head's product left
them (bf16 for the models' default dtype) and the backward pass starts from
them and a float32 log-sum-exp a row.

**A model with several heads.** Where a model hands back a dict, ``'logits'``
is one array or a tuple of them (``models.LatentMoELM``: the next token and
the second next) and ``'metrics'`` arrays of its own that the step passes on
in its ``metrics`` (``expert_load``, ``layout_fallbacks``). The labels of a
tuple of heads are a tuple of ``(labels, weights)`` pairs, one a head; the
step's loss is the sum over the heads of ``sum(weights * loss)``, so a head's
mean, its mask and its coefficient are all in its weights. Each head goes
through the same op (one ``step.loss_plan`` instant a distinct plan). A model
that hands back an array runs the program it always ran.
"""

from typing import Any

import jax
import jax.numpy as jnp
import optax
from flax.training import train_state
from jax.sharding import NamedSharding, PartitionSpec

from petastorm_tpu.ops.cross_entropy import softmax_cross_entropy
from petastorm_tpu.trace import StepProgram


class TrainState(train_state.TrainState):
    batch_stats: Any = None


def _param_spec(path, value, mesh):
    """Sharding rule: classifier-head kernel is tensor-parallel over 'model';
    everything else replicated."""
    if mesh is None or 'model' not in mesh.axis_names:
        return PartitionSpec()
    names = [getattr(p, 'key', getattr(p, 'name', '')) for p in path]
    if 'head' in names and names[-1] == 'kernel' and value.ndim == 2:
        return PartitionSpec(None, 'model')
    return PartitionSpec()


def create_train_state(rng, model, input_shape, mesh=None, learning_rate=1e-3,
                       momentum=0.9, tx=None, param_spec_fn=None,
                       example_input=None):
    """Initialize (optionally mesh-sharded) training state.

    :param param_spec_fn: ``(path, value, mesh) -> PartitionSpec`` sharding
        rule; defaults to :func:`_param_spec` (classifier-head tensor
        parallelism). Use ``transformer_param_spec`` for Megatron-style TP
        over a TransformerLM.
    :param example_input: exact init input (defaults to
        ``jnp.ones(input_shape, float32)`` — pass int token arrays for LMs).
    """
    if example_input is None:
        example_input = jnp.ones(input_shape, jnp.float32)
    variables = model.init(rng, example_input, train=False)
    params = variables['params']
    batch_stats = variables.get('batch_stats')
    if tx is None:
        tx = optax.sgd(learning_rate, momentum=momentum)
    state = TrainState.create(apply_fn=model.apply, params=params, tx=tx,
                              batch_stats=batch_stats)
    if mesh is not None:
        spec_fn = param_spec_fn or _param_spec

        def place(path, leaf):
            return jax.device_put(leaf, NamedSharding(mesh, spec_fn(path, leaf, mesh)))
        state = jax.tree_util.tree_map_with_path(place, state)
    return state


def transformer_param_spec(path, value, mesh):
    """Megatron-style tensor parallelism for :class:`TransformerLM`.

    Over the mesh's 'model' axis: attention q/k/v projections shard by head,
    the attention output projection by its head input, the MLP up-projection
    by its (4x) output features and the down-projection by its input
    features, and the vocabulary head by vocab. Everything else (embeddings,
    norms, biases) replicates. XLA inserts the activation all-reduces from
    these annotations — the scaling-book recipe, no hand-rolled collectives.
    """
    if mesh is None or 'model' not in mesh.axis_names:
        return PartitionSpec()
    names = [str(getattr(p, 'key', getattr(p, 'name', ''))) for p in path]
    joined = '/'.join(names)
    if names[-1] != 'kernel':
        return PartitionSpec()
    n_model = mesh.shape['model']

    def fits(dim):
        return value.shape[dim] % n_model == 0

    if ('attn/query' in joined or 'attn/key' in joined
            or 'attn/value' in joined) and value.ndim == 3 and fits(1):
        return PartitionSpec(None, 'model', None)      # [d_model, H, Dh]
    if 'attn/out' in joined and value.ndim == 3 and fits(0):
        return PartitionSpec('model', None, None)      # [H, Dh, d_model]
    if 'head' in names and value.ndim == 2 and fits(1):
        return PartitionSpec(None, 'model')            # [d_model, vocab]
    # The Block MLP pair, matched by path (never by shape, which would
    # mis-shard unrelated future Dense layers): Dense_0 is the column-
    # parallel up-projection, Dense_1 the row-parallel down-projection.
    if 'Dense_0' in names and value.ndim == 2 and fits(1):
        return PartitionSpec(None, 'model')            # [d, ratio*d]
    if 'Dense_1' in names and value.ndim == 2 and fits(0):
        return PartitionSpec('model', None)            # [ratio*d, d]
    return PartitionSpec()


def _model_outputs(out):
    """``(logits, metrics of the model's own)`` of what a model handed back."""
    if isinstance(out, dict):
        return out['logits'], dict(out.get('metrics', {}))
    return out, {}


def summed_loss(logits, labels):
    """``(loss, hit)``: the batch's mean loss and each row's hit for one
    array of logits; for a tuple of heads the sum of ``sum(weights * loss)``
    over ``labels``' ``(labels, weights)`` pairs, and the first head's hit."""
    if not isinstance(logits, (tuple, list)):
        loss, hit = softmax_cross_entropy(logits, labels)
        return loss.mean(), hit
    if len(logits) != len(labels):
        raise ValueError('{} heads against {} (labels, weights) pairs'.format(
            len(logits), len(labels)))
    total, first_hit = 0.0, None
    for head, (target, weights) in zip(logits, labels):
        loss, hit = softmax_cross_entropy(head, target)
        total = total + jnp.sum(loss * weights)
        first_hit = hit if first_hit is None else first_hit
    return total, first_hit


def make_train_step(mesh=None, batch_axis='data'):
    """Build a jitted train step ``(state, images, labels) -> (state, metrics)``."""
    return StepProgram(jax.jit(
        make_train_step_fn(mesh=mesh, batch_axis=batch_axis),
        donate_argnums=(0,)))


def make_scan_train_step(mesh=None, batch_axis='data', microbatches=8,
                         preprocess=None):
    """Build a jitted multi-step trainer: one call runs ``microbatches``
    sequential SGD steps via ``lax.scan``.

    TPU-first shape: instead of one Python dispatch + one host->HBM transfer
    per step, the input pipeline delivers a K-times-larger superbatch and the
    whole K-step loop compiles into a single XLA program
    (``lax.scan`` — compiler-friendly control flow, no per-step dispatch
    latency). The math is identical to calling the per-step trainer K times:
    gradients apply sequentially, microbatch i+1 sees the params updated by
    microbatch i. Metrics are averaged over the K microbatches.

    ``preprocess(images_microbatch)`` (optional) runs inside the compiled
    scan body — e.g. the uint8 -> float normalize, so transfers ride h2d
    as uint8 and the cast fuses into the first conv.

    ``(state, images [K*B, ...], labels [K*B]) -> (state, metrics)``.
    """
    inner = make_train_step_fn(mesh=mesh, batch_axis=batch_axis)

    def scan_train(state, images, labels):
        total = images.shape[0]
        if total % microbatches:
            raise ValueError('superbatch {} not divisible by microbatches {}'
                             .format(total, microbatches))
        micro = total // microbatches
        images = images.reshape((microbatches, micro) + images.shape[1:])
        labels = labels.reshape((microbatches, micro) + labels.shape[1:])

        def body(state, xs):
            imgs, labs = xs
            if preprocess is not None:
                imgs = preprocess(imgs)
            state, metrics = inner(state, imgs, labs)
            return state, (metrics['loss'], metrics['accuracy'])

        state, (losses, accs) = jax.lax.scan(body, state, (images, labels))
        return state, {'loss': losses.mean(), 'accuracy': accs.mean(),
                       'last_loss': losses[-1]}

    return StepProgram(jax.jit(scan_train, donate_argnums=(0,)))


def make_train_step_fn(mesh=None, batch_axis='data'):
    """The un-jitted train step body (shared by ``make_train_step`` and
    ``make_scan_train_step``). ``metrics`` holds the batch's mean ``loss``
    and its ``accuracy``, both out of the fused loss's one look at the
    logits."""

    def train_step(state, images, labels):
        if mesh is not None:
            images = jax.lax.with_sharding_constraint(
                images, NamedSharding(mesh, PartitionSpec((batch_axis,))))
            labels = jax.lax.with_sharding_constraint(
                labels, NamedSharding(mesh, PartitionSpec((batch_axis,))))

        def loss_fn(params):
            variables = {'params': params}
            if state.batch_stats is not None:
                variables['batch_stats'] = state.batch_stats
                logits, updates = state.apply_fn(variables, images, train=True,
                                                 mutable=['batch_stats'])
                new_batch_stats = updates['batch_stats']
            else:
                logits = state.apply_fn(variables, images, train=True)
                new_batch_stats = None
            logits, extra = _model_outputs(logits)
            with jax.named_scope('loss'):
                loss, hit = summed_loss(logits, labels)
                accuracy = jnp.mean(hit)
            return loss, (accuracy, new_batch_stats, extra)

        (loss, (accuracy, new_batch_stats, extra)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        # The gradients' casts, the optimizer's update and its application.
        with jax.named_scope('optimizer'):
            state = state.apply_gradients(grads=grads)
        if new_batch_stats is not None:
            state = state.replace(batch_stats=new_batch_stats)
        return state, dict(extra, loss=loss, accuracy=accuracy)

    return train_step


def make_eval_step():
    def eval_step(state, images, labels):
        variables = {'params': state.params}
        if state.batch_stats is not None:
            variables['batch_stats'] = state.batch_stats
        logits, extra = _model_outputs(
            state.apply_fn(variables, images, train=False))
        loss, hit = summed_loss(logits, labels)
        return dict(extra, loss=loss, accuracy=jnp.mean(hit))

    return jax.jit(eval_step)
