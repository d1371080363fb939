"""``ops.swiglu.swiglu``: its output and all four gradients against
``jax.vjp`` of the plain ``down(silu(x W_gate) · (x W_up))`` (float32 and
bf16, bare, under ``nn.remat`` and under ``jax.jit``), what it keeps between
the passes, ``models.hybrid.SwiGLU``'s parameter tree against three dense
layers', its ``step.ffn_plan`` instant, and a SwiGLU block's step compiled
for a described v5e at ``[8192, 3840 -> 11008]``: no backward product fuses
the elementwise work that makes its operands."""

import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax

from petastorm_tpu import trace
from petastorm_tpu.models import hybrid
from petastorm_tpu.models.train import TrainState, make_train_step
from petastorm_tpu.ops import swiglu as sw


def plain(x, w_gate, w_up, w_down):
    """The feed-forward as three dense layers compute it, differentiated by
    jax."""
    def dense(a, w):
        return lax.dot_general(a, w, (((a.ndim - 1,), (0,)), ((), ())))
    return dense(nn.silu(dense(x, w_gate)) * dense(x, w_up), w_down)


class DenseSwiGLU(nn.Module):
    """``SwiGLU`` as three ``nn.DenseGeneral`` layers held it."""
    d_ff: int
    dtype: object = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        def dense(a, features, name):
            return nn.DenseGeneral(features, use_bias=False, dtype=self.dtype,
                                   name=name)(a)
        return dense(nn.silu(dense(x, self.d_ff, 'gate'))
                     * dense(x, self.d_ff, 'up'), x.shape[-1], 'down')


CASES = {
    # name: (x's shape, d_ff, dtype, what the call runs under)
    'f32': ((2, 24, 32), 80, jnp.float32, None),
    'f32_remat': ((2, 24, 32), 80, jnp.float32, 'remat'),
    'f32_rows': ((48, 32), 80, jnp.float32, None),
    'bf16': ((1, 64, 128), 384, jnp.bfloat16, None),
    'bf16_remat': ((1, 64, 128), 384, jnp.bfloat16, 'remat'),
    'bf16_jit': ((2, 32, 128), 256, jnp.bfloat16, 'jit'),
}


def _f32(a):
    return np.asarray(jnp.asarray(a, jnp.float32))


def _operands(shape, d_ff, dtype, seed):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    d = shape[-1]
    x = jax.random.normal(keys[0], shape, jnp.float32)
    w_gate = jax.random.normal(keys[1], (d, d_ff)) * d ** -0.5
    w_up = jax.random.normal(keys[2], (d, d_ff)) * d ** -0.5
    w_down = jax.random.normal(keys[3], (d_ff, d)) * d_ff ** -0.5
    dy = jax.random.normal(keys[4], shape, jnp.float32)
    return tuple(a.astype(dtype) for a in (x, w_gate, w_up, w_down, dy))


@pytest.mark.parametrize('case', sorted(CASES))
def test_output_and_gradients_match_autodiff_of_the_expression(case):
    shape, d_ff, dtype, wrap = CASES[case]
    x, w_gate, w_up, w_down, dy = _operands(shape, d_ff, dtype, len(case))

    def vjp_of(f):
        if wrap == 'remat':
            f = jax.checkpoint(f)

        def run(*args):
            out, pullback = jax.vjp(f, *args)
            return (out,) + pullback(dy)
        return jax.jit(run) if wrap == 'jit' else run

    got = vjp_of(sw.swiglu)(x, w_gate, w_up, w_down)
    want = vjp_of(plain)(x, w_gate, w_up, w_down)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype == dtype and a.shape == b.shape
    if wrap is None:            # the forward pass is the expression itself
        np.testing.assert_array_equal(_f32(got[0]), _f32(want[0]))
    # float32: the same arithmetic in another order; bf16: the operands of
    # the products rounded once where autodiff rounds after every
    # operation, so a gradient differs by a unit of bf16's last place of its
    # largest element (2^-7 of it; 2^-6 allowed).
    tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -6
    for a, b in zip(got, want):
        scale = float(np.max(np.abs(_f32(b))))
        np.testing.assert_allclose(_f32(a), _f32(b), rtol=tol,
                                   atol=tol * scale)


def test_the_residuals_are_x_g_u_and_the_weights():
    """What lives between the passes is ``x``, ``g`` and ``u`` in their
    dtype and the three weights, no float32 array and no ``h``; the plan
    counts it."""
    x, w_gate, w_up, w_down, _ = _operands((1, 16, 32), 48, jnp.bfloat16, 0)
    _, pullback = jax.vjp(sw.swiglu, x, w_gate, w_up, w_down)
    kept = jax.tree_util.tree_leaves(pullback)
    assert sorted((a.shape, a.dtype.name) for a in kept) == sorted([
        ((1, 16, 32), 'bfloat16'), ((1, 16, 48), 'bfloat16'),
        ((1, 16, 48), 'bfloat16'), ((32, 48), 'bfloat16'),
        ((32, 48), 'bfloat16'), ((48, 32), 'bfloat16')])
    plan = sw.ffn_plan(16, 32, 48, jnp.bfloat16)
    assert plan['residual_bytes'] == sum(a.nbytes for a in kept)
    assert plan == {'rows': 16, 'd_model': 32, 'd_ff': 48,
                    'dtype': 'bfloat16', 'backward': 'operands formed once',
                    'residual_bytes': 2 * (16 * 128 + 3 * 32 * 48)}


@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32])
def test_the_parameter_tree_and_its_start_are_three_dense_layers(dtype):
    """``gate``, ``up`` and ``down`` hold a ``kernel`` each at the path,
    shape, dtype and initial value three ``nn.DenseGeneral`` layers gave
    them, and the module computes what they computed."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32)).astype(dtype)
    mine = hybrid.SwiGLU(96, dtype=dtype)
    theirs = DenseSwiGLU(96, dtype=dtype)
    params = mine.init(jax.random.PRNGKey(2), x)
    dense = theirs.init(jax.random.PRNGKey(2), x)
    assert jax.tree.structure(params) == jax.tree.structure(dense)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), dense)
    assert {k: v['kernel'].shape for k, v in params['params'].items()} == {
        'gate': (32, 96), 'up': (32, 96), 'down': (96, 32)}
    jax.tree.map(np.testing.assert_array_equal, params, dense)
    np.testing.assert_array_equal(_f32(mine.apply(params, x)),
                                  _f32(theirs.apply(params, x)))


class _TwoSizes(nn.Module):
    """Two blocks of one SwiGLU, recomputed, and a third of another width:
    two distinct plans among three calls traced three ways."""

    @nn.compact
    def __call__(self, tokens, train=True):
        x = nn.Embed(32, 16, name='embed')(tokens)
        for i, d_ff in enumerate((40, 40, 24)):
            x = x + nn.remat(hybrid.SwiGLU)(d_ff, name='mlp_%d' % i)(x)
        return nn.Dense(32, name='head')(x)


def test_one_plan_instant_a_distinct_plan(monkeypatch):
    """The ring has one ``step.ffn_plan`` a distinct plan though every
    call was traced forward, recomputed and differentiated."""
    monkeypatch.setattr(sw, '_plans_reported', set())
    tracer = trace.Tracer(spill_dir=False)
    previous = trace.set_global_tracer(tracer)
    try:
        model = _TwoSizes()
        tokens = jnp.arange(2 * 9).reshape(2, 9) % 32
        params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])['params']
        state = TrainState.create(apply_fn=model.apply, params=params,
                                  tx=optax.adamw(1e-3))
        state, metrics = make_train_step()(
            state.replace(step=jnp.zeros((), jnp.int32)), tokens[:, :-1],
            tokens[:, 1:])
        assert np.isfinite(float(metrics['loss']))
    finally:
        trace.set_global_tracer(previous)
    plans = [r for r in tracer.records() if r[0] == 'step.ffn_plan']
    assert all(r[1] == 'step' and r[3] is None for r in plans)  # instants
    assert sorted((r[7]['rows'], r[7]['d_model'], r[7]['d_ff'], r[7]['dtype'],
                   r[7]['backward']) for r in plans) == [
        (16, 16, 24, 'bfloat16', 'operands formed once'),
        (16, 16, 40, 'bfloat16', 'operands formed once')]


# -- compiled for the chip that is described, not attached ---------------

@pytest.fixture(scope='module')
def v5e():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 - whatever says there is no compiler
        pytest.skip('no v5e:2x2 topology can be described here: {}'.format(e))


_COMPUTATION = re.compile(r'^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\)\s*->.*\{\s*$')
_INSTRUCTION = re.compile(r'^\s+(?:ROOT\s+)?%?([^\s=]+) = ')
#: What may make a product's operand inside its fusion: the operand read as
#: it lies, its layout or its dtype changed.
_AS_IT_LIES = {'parameter', 'constant', 'bitcast', 'reshape', 'copy',
               'transpose', 'convert', 'broadcast', 'fusion'}


def _computations(text):
    """``{computation: {instruction: (result, opcode, operands, callee)}}``
    of an optimized HLO module's text."""
    out, current = {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            current = out[m.group(1)] = {}
            continue
        if line.startswith('}'):
            current = None
            continue
        m = _INSTRUCTION.match(line) if current is not None else None
        if m:
            name, at, depth = m.group(1), m.end(), 0
            for end in range(at, len(line)):     # the result, a tuple's too
                depth += (line[end] == '(') - (line[end] == ')')
                if depth == 0 and line[end] == ' ':
                    break
            result = line[at:end]
            opcode, _, rest = line[end + 1:].partition('(')
            callee = re.search(r'\bcalls=%?([^\s,)}]+)', rest)
            operands = re.findall(r'%([\w.\-]+)', rest.split('), ')[0])
            current[name] = (result, opcode, operands,
                             callee.group(1) if callee else None)
    return out


def _operand_producers(computations, computation):
    """The opcodes and results of every instruction that feeds a
    convolution of ``computation``, through the fusions nested in it."""
    body = computations[computation]
    found, seen = [], set()

    def walk(names, body):
        for name in names:
            if name in seen or name not in body:
                continue
            seen.add(name)
            result, opcode, operands, callee = body[name]
            found.append((opcode, result))
            if opcode == 'fusion' and callee in computations:
                inner = computations[callee]
                roots = [n for n in inner if inner[n][1] != 'parameter']
                walk(roots, inner)
            walk(operands, body)

    for name, (_, opcode, operands, _) in body.items():
        if opcode == 'convolution':
            walk(operands, body)
    return found


def test_a_swiglu_step_for_a_v5e_forms_the_backward_operands_once(v5e):
    """One recomputed block, ``x + norm(swiglu(x))`` at ``[8192, 3840 ->
    11008]``, with AdamW, compiled for a v5e: every product of the
    SwiGLU's backward pass reads its operands as they lie in HBM (a
    layout's or a dtype's change at most), so no ``exponential``, no
    elementwise ``[8192, 11008]`` work and no norm's backward is fused into
    any of them; the elementwise pass is one fusion of its own."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from petastorm_tpu.models import scopes
    from petastorm_tpu.models.train import make_train_step_fn

    mesh = Mesh(np.array(v5e.devices[:1]), ('data',))

    class Block(nn.Module):
        @nn.compact
        def __call__(self, x):
            with jax.named_scope('mlp'):
                y = hybrid.SwiGLU(11008, name='mlp')(x)
                return x + hybrid.RMSNorm(name='mlp_norm')(y)

    class Model(nn.Module):
        @nn.compact
        def __call__(self, tokens, train=True):
            x = nn.Embed(64, 3840, name='embed')(tokens).astype(jnp.bfloat16)
            x = nn.remat(Block)(name='block_0')(x)
            return nn.Dense(64, name='head')(x)

    model = Model()
    replicated = NamedSharding(mesh, PartitionSpec())
    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=NamedSharding(
        mesh, PartitionSpec('data')))

    def fresh(rng):
        params = model.init(rng, jnp.zeros((1, 8192), jnp.int32))['params']
        return TrainState.create(apply_fn=model.apply, params=params,
                                 tx=optax.adamw(1e-3))

    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=replicated),
        jax.eval_shape(fresh, jax.random.PRNGKey(0)))
    text = jax.jit(make_train_step_fn(mesh=mesh)).lower(
        state, tokens, tokens).compile().as_text()
    table = scopes.parse_hlo_scopes(text)['instructions']
    computations = _computations(text)
    entry = {name: row for body in computations.values()
             for name, row in body.items()}
    products = [name for name, r in table.items()
                if r['pass'] == 'backward' and r['opcode'] == 'fusion'
                and re.search(r'/mlp/(gate|up|down)/dot_general$', r['path'])]
    # dh, h·dy, two of x·d and two of d·W: six products, each a fusion
    assert len(products) == 6, products
    for name in products:
        assert 'kind=kOutput' in text.split('%' + name + ' = ', 1)[1].split(
            '\n', 1)[0]
        producers = _operand_producers(computations, entry[name][3])
        assert {op for op, _ in producers} <= _AS_IT_LIES, (name, producers)
        assert not any(op == 'exponential' for op, _ in producers)
    # the elementwise pass: one fusion in the SwiGLU's scope, under ffn.dense
    passes = [r for r in table.values() if r['pass'] == 'backward'
              and '/mlp/silu/' in r['path'] + '/' and r['opcode'] == 'fusion']
    assert len(passes) == 1, passes
    assert {r['part'] for r in passes} == {'ffn.dense'}
