"""``Tracer.op_scopes``: the instructions of a compiled train step put down to
the model's parts and passes by the scopes they carry. A two-block remat
model through ``make_train_step`` (passes, parts, a mixed fusion, containers,
no compilation, no array kept, a second signature), the parsing on literal
``op_name``s and a literal HLO module, and one case a model family at a tiny
size: hardly anything in ``other``, every name of the rules met."""

import collections
import gc
import importlib.util
import json
import os
import re
import weakref

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from petastorm_tpu import trace
from petastorm_tpu.models import scopes
from petastorm_tpu.models.train import (TrainState, make_scan_train_step,
                                        make_train_step)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = os.path.join(ROOT, 'perfbench', 'tests', 'tiny')


@pytest.fixture
def tracer():
    """A ring of its own as the global tracer, with compilations watched."""
    mine = trace.Tracer()
    previous = trace.set_global_tracer(mine)
    trace.watch_jax_compiles()
    yield mine
    trace.set_global_tracer(previous)


# -- a two-block remat model ---------------------------------------------------------

class Layer(nn.Module):
    width: int

    @nn.compact
    def __call__(self, x):
        with jax.named_scope('mixer'):
            y = nn.LayerNorm(name='norm')(x)
            q = nn.Dense(self.width, name='attn')(y)
            # a loop whose trip count is data, outside differentiation
            steps = jnp.sum(jax.lax.stop_gradient(q) > 0) % 3 + 1
            _, scale = jax.lax.while_loop(
                lambda c: c[0] < steps, lambda c: (c[0] + 1, c[1] * 0.5),
                (jnp.zeros((), steps.dtype), jnp.ones(())))
            x = x + q * scale
        with jax.named_scope('mlp'):
            y = nn.Dense(4 * self.width, name='up')(nn.LayerNorm()(x))
            y = jax.lax.cond(jnp.sum(y) > 0, nn.gelu, nn.silu, y)
            return x + nn.Dense(self.width, name='down')(y)


class TwoBlocks(nn.Module):
    vocab: int = 32
    width: int = 16

    @nn.compact
    def __call__(self, tokens, train=True):
        x = nn.Embed(self.vocab, self.width, name='embed')(tokens)
        for i in range(2):
            x = nn.remat(Layer)(self.width, name='layers_{}'.format(i))(x)
        x = nn.LayerNorm(name='final_norm')(x)
        return nn.Dense(self.vocab, name='head')(x)


def fresh_state(model, tokens):
    params = model.init(jax.random.PRNGKey(0), tokens)['params']
    state = TrainState.create(apply_fn=model.apply, params=params,
                              tx=optax.adamw(1e-3))
    return state.replace(step=jnp.zeros((), jnp.int32))


@pytest.fixture
def stepped(tracer):
    """One step of the model through ``make_train_step``."""
    model = TwoBlocks()
    tokens = jnp.arange(2 * 9).reshape(2, 9) % model.vocab
    state = fresh_state(model, tokens)
    leaf = weakref.ref(state.params['head']['kernel'])
    step = make_train_step()
    state, metrics = step(state, tokens[:, :-1], tokens[:, 1:])
    jax.block_until_ready(metrics['loss'])
    return tracer, step, state, tokens, leaf


def compile_spans(tracer):
    return [r for r in tracer.records() if r[0] == 'jax.compile']


def test_the_table_has_every_pass_and_the_declared_parts(stepped):
    tracer, _, _, _, _ = stepped
    tables = tracer.op_scopes()
    assert list(tables) == ['train_step']
    table = tables['train_step']
    assert table['module'] == 'jit_train_step'
    rows = table['instructions'].values()
    assert {r['pass'] for r in rows} >= {'forward', 'recompute', 'backward',
                                         'update'}
    assert {r['part'] for r in rows} >= {'embed', 'mixer', 'ffn.dense',
                                         'head', 'loss', 'optimizer'}
    assert {r['part'] for r in rows} <= set(scopes.PARTS)
    for r in rows:
        assert (r['pass'] == 'update') == (r['part'] == 'optimizer')
        assert set(r) == {'opcode', 'result', 'part', 'pass', 'path',
                          'parts_fused'}
    # the recomputed forward is the blocks': nothing of the head or the loss
    assert {r['part'] for r in rows if r['pass'] == 'recompute'} <= {
        'mixer', 'ffn.dense', 'unscoped'}
    paths = {r['path'] for r in rows}
    assert any(p.startswith('TwoBlocks/layers_1/mixer/attn/') for p in paths)
    assert any(p.startswith('optimizer/') for p in paths)
    assert not any('jit(' in p or 'jvp(' in p or 'transpose(' in p
                   or 'checkpoint' in p or 'rematted' in p for p in paths)


def test_a_fusion_says_what_it_fused_and_containers_keep_their_opcode(stepped):
    tracer, _, _, _, _ = stepped
    rows = tracer.op_scopes()['train_step']['instructions']
    fusions = [r for r in rows.values() if r['opcode'] == 'fusion']
    assert fusions and all(r['parts_fused'] is not None for r in fusions)
    assert all(r['parts_fused'] is None for r in rows.values()
               if r['opcode'] != 'fusion')
    assert any(len(r['parts_fused']) > 1 for r in fusions)
    loops = [r for r in rows.values() if r['opcode'] == 'while']
    choices = [r for r in rows.values() if r['opcode'] == 'conditional']
    assert loops and {r['part'] for r in loops} == {'mixer'}
    assert choices and {r['part'] for r in choices} == {'ffn.dense'}
    assert set(scopes.CONTAINERS) == {'while', 'conditional', 'call'}
    # what never is a device event is not in the table
    assert not {r['opcode'] for r in rows.values()} & {
        'parameter', 'constant', 'tuple', 'get-tuple-element', 'bitcast'}


def test_op_scopes_compiles_nothing_and_is_computed_once(stepped):
    tracer, _, _, _, _ = stepped
    before = len(compile_spans(tracer))
    assert before >= 1                      # the step's own compilation
    first = tracer.op_scopes()
    assert len(compile_spans(tracer)) == before
    assert tracer.op_scopes()['train_step'] is first['train_step']


def test_no_array_is_kept_and_the_donated_state_is_collectable(stepped):
    tracer, step, state, _, leaf = stepped
    tracer.op_scopes()
    for signature, _ in step._tables.values():
        for x in jax.tree_util.tree_leaves(signature):
            assert isinstance(x, jax.ShapeDtypeStruct)
    gc.collect()
    assert leaf() is None                   # the first step's donated leaf
    del state
    gc.collect()
    assert tracer.op_scopes()['train_step']['instructions']


def test_the_callable_forwards_the_jit_object(stepped):
    _, step, state, tokens, _ = stepped
    assert isinstance(step, trace.StepProgram)
    lowered = step.lower(state, tokens[:, :-1], tokens[:, 1:])
    assert 'train_step' in lowered.as_text()[:400]
    assert step._cache_size() == 1
    assert step.__wrapped__ is step._jitted
    with pytest.raises(AttributeError):
        step.no_such_attribute


def test_one_instant_and_one_table_a_signature(stepped):
    tracer, step, state, tokens, _ = stepped

    def instants():
        return [r for r in tracer.records() if r[0] == 'step.program']

    assert len(instants()) == 1
    (name, layer, _, dur, _, _, _, args), = instants()
    assert (name, layer, dur) == ('step.program', 'step', None)
    assert args['function'] == 'train_step' and args['program'] == 'train_step'
    assert args['leaves'] == len(jax.tree_util.tree_leaves(state)) + 2
    assert args['bytes'] == sum(x.nbytes for x in jax.tree_util.tree_leaves(
        (state, tokens[:, :-1], tokens[:, 1:])))
    state, _ = step(state, tokens[:, :-1], tokens[:, 1:])   # the same again
    assert len(instants()) == 1
    wider = jnp.concatenate([tokens, tokens], axis=1)
    state, _ = step(state, wider[:, :-1], wider[:, 1:])
    assert [r[7]['program'] for r in instants()] == ['train_step',
                                                     'train_step#2']
    tables = tracer.op_scopes()
    assert list(tables) == ['train_step', 'train_step#2']
    assert tables['train_step#2']['instructions']


def test_a_tracer_switched_off_keeps_nothing():
    off = trace.NullTracer()
    assert off.op_scopes() is None
    previous = trace.set_global_tracer(off)
    try:
        model = TwoBlocks()
        tokens = jnp.arange(2 * 5).reshape(2, 5) % model.vocab
        state, metrics = make_train_step()(
            fresh_state(model, tokens), tokens[:, :-1], tokens[:, 1:])
        assert np.isfinite(float(metrics['loss']))
        assert off.op_scopes() is None and not off._steps
    finally:
        trace.set_global_tracer(previous)


def test_the_scan_step_is_a_program_too(tracer):
    model = TwoBlocks()
    tokens = jnp.arange(4 * 9).reshape(4, 9) % model.vocab
    step = make_scan_train_step(microbatches=2)
    _, metrics = step(fresh_state(model, tokens), tokens[:, :-1],
                      tokens[:, 1:])
    assert np.isfinite(float(metrics['loss']))
    table = tracer.op_scopes()['scan_train']
    assert {'update', 'backward'} <= {
        r['pass'] for r in table['instructions'].values()}


def test_a_call_under_another_trace_is_no_program(tracer):
    model = TwoBlocks()
    tokens = jnp.arange(2 * 5).reshape(2, 5) % model.vocab
    inner = make_train_step()
    outer = jax.jit(lambda s, x, y: inner(s, x, y)[1]['loss'])
    assert np.isfinite(float(outer(fresh_state(model, tokens),
                                   tokens[:, :-1], tokens[:, 1:])))
    assert tracer.op_scopes() == {}


def test_the_newest_steps_are_kept_and_an_older_one_is_collectable(tracer):
    """The tracer keeps a step past its caller's last reference (a run's
    metrics are read after the step went), but only the newest
    ``MAX_STEP_PROGRAMS``: a process that builds steps in a loop does not
    keep every ``jax.jit`` object it ever made."""
    model = TwoBlocks()
    tokens = jnp.arange(2 * 5).reshape(2, 5) % model.vocab
    state = fresh_state(model, tokens)
    first = None
    for i in range(trace.MAX_STEP_PROGRAMS + 1):
        step = make_train_step()
        state, _ = step(state, tokens[:, :-1], tokens[:, 1:])
        if first is None:
            first = weakref.ref(step), weakref.ref(step._jitted)
        del step
    gc.collect()
    assert first[0]() is None and first[1]() is None
    assert len(tracer._steps) == trace.MAX_STEP_PROGRAMS
    # names are never given twice: the first step's went with it
    assert list(tracer.op_scopes()) == [
        'train_step#{}'.format(i + 1)
        for i in range(1, trace.MAX_STEP_PROGRAMS + 1)]


def test_the_signature_and_the_table_live_on_the_step(stepped):
    tracer, step, _, _, _ = stepped
    assert list(tracer._steps) == [step]
    assert step.op_scopes() == tracer.op_scopes()
    (signature, table), = step._tables.values()
    assert table is tracer.op_scopes()['train_step']
    assert not hasattr(tracer, '_programs')


# -- the parsing, on literals --------------------------------------------------------

@pytest.mark.parametrize('op_name, names, primitive, which', [
    ('jit(train_step)/jvp(M)/layers_0/up/dot_general',
     ['M', 'layers_0', 'up'], 'dot_general', 'forward'),
    ('jit(train_step)/transpose(jvp(M))/jvp(M)/checkpoint/'
     'rematted_computation/layers_0/norm/mul',
     ['M', 'layers_0', 'norm'], 'mul', 'recompute'),
    ('jit(train_step)/transpose(jvp(M))/checkpoint/layers_1/down/transpose',
     ['M', 'layers_1', 'down'], 'transpose', 'backward'),
    ('jit(train_step)/optimizer/mul', ['optimizer'], 'mul', 'forward'),
    ('jit(train_step)/jvp(M)/embed/jit(_take)/gather',
     ['M', 'embed'], 'gather', 'forward'),
    ('jit(train_step)/jvp(M)/embed/jit(_take)', ['M', 'embed'], '',
     'forward'),
    ('jit(train_step)/jvp(M)/block_1/moe/moe/cond/branch_1_fun/moe/'
     'pallas_call', ['M', 'block_1', 'moe'], 'pallas_call', 'forward'),
    ('jit(train_step)/jvp(M)/block_0/attn/while/body/cond/branch_0_fun/add',
     ['M', 'block_0', 'attn'], 'add', 'forward'),
    ('jit(train_step)/jvp(loss)/custom_vjp_call/reduce_max', ['loss'],
     'reduce_max', 'forward'),
    ('jit(train_step)/transpose(jvp(M))/jvp(jit(_where))/select_n', ['M'],
     'select_n', 'backward'),
])
def test_scope_of(op_name, names, primitive, which):
    assert scopes.scope_of(op_name) == (names, primitive, which)


@pytest.mark.parametrize('path, part', [
    ('TransformerLM/block_0/mixer/LayerNorm_0', 'mixer'),
    ('TransformerLM/block_0/mixer/attn/query', 'mixer'),
    ('TransformerLM/block_0/mlp/Dense_1', 'ffn.dense'),
    ('TransformerLM/embed/pos_embed', 'embed'),
    ('TransformerLM/head/LayerNorm_0', 'head'),
    ('HybridLM/block_2/mixer/gdn/transform', 'mixer'),
    ('HybridLM/block_2/mlp/mlp_norm', 'ffn.dense'),
    ('LingHybridLM/block_3/moe/ffn_norm', 'ffn.routed'),
    ('LingHybridLM/block_3/moe/shared/gate', 'ffn.shared'),
    ('LingHybridLM/block_3/moe/routing', 'ffn.routed'),
    ('LingHybridLM/block_0/mlp/ffn_norm', 'ffn.dense'),
    ('LingHybridLM/block_5/mixer/attn/rotary', 'mixer'),
    ('LatentMoELM/block_1/attn_hc/hc', 'streams'),
    ('LatentMoELM/block_1/attn_hc/attn_norm', 'mixer'),
    ('LatentMoELM/block_1/ffn_hc/moe/token_sums', 'ffn.routed'),
    ('LatentMoELM/block_1/ffn_hc/norm', 'streams'),
    ('LatentMoELM/streams', 'streams'),
    ('LatentMoELM/mtp_0/eh_proj', 'head'),
    ('LatentMoELM/mtp_0/block/ffn_hc/moe/router', 'ffn.routed'),
    ('LatentMoELM/final_norm', 'head'),
    ('ResNet/BottleneckBlock_3/BatchNorm_1', 'norm'),
    ('ResNet/BottleneckBlock_3/Conv_2', 'body'),
    ('ResNet/BottleneckBlock_3', 'body'),
    ('ResNet/bn_init', 'norm'),
    ('ResNet/stem', 'body'),
    ('optimizer', 'optimizer'),
    ('loss', 'loss'),
    ('LingHybridLM', 'other'),
    ('', 'other'),
])
def test_part_of(path, part):
    assert scopes.part_of(path) == part
    assert scopes.part_of(path.split('/')) == part


HLO = '''HloModule jit_step, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="jit(step)/jvp(M)/head/reduce_sum"}
}

%fused_computation (p0: f32[8], p1: f32[8]) -> (f32[8], f32[8]) {
  %p0 = f32[8]{0} parameter(0)
  %p1 = f32[8]{0} parameter(1)
  %dot.1 = f32[8]{0} multiply(%p0, %p1), metadata={op_name="jit(step)/transpose(jvp(M))/block_0/mlp/up/dot_general"}
  %mul.2 = f32[8]{0} multiply(%dot.1, %p1), metadata={op_name="jit(step)/optimizer/mul"}
  ROOT %tuple.3 = (f32[8]{0}, f32[8]{0}) tuple(%dot.1, %mul.2)
}

%fused_computation.1 (p0.1: f32[8]) -> f32[8] {
  %p0.1 = f32[8]{0} parameter(0)
  %neg.4 = f32[8]{0} negate(%p0.1), metadata={op_name="jit(step)/jvp(M)/embed/neg"}
  ROOT %bitcast.5 = f32[8]{0} bitcast(%neg.4)
}

%body.1 (c: (s32[], f32[8])) -> (s32[], f32[8]) {
  %c = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%c), index=1
  %exp.6 = f32[8]{0} exponential(%gte.1), metadata={op_name="jit(step)/jvp(M)/block_0/attn/while/body/exp"}
  %copy.7 = f32[8]{0} copy(%exp.6)
  ROOT %tuple.8 = (s32[], f32[8]{0}) tuple(%gte.1, %copy.7)
}

%cond.1 (c.1: (s32[], f32[8])) -> pred[] {
  %c.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt.1 = pred[] constant(true)
}

%branch_a (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  ROOT %tanh.1 = f32[8]{0} tanh(%x), metadata={op_name="jit(step)/jvp(M)/block_0/moe/cond/branch_0_fun/every_token/tanh"}
}

%branch_b (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0)
  ROOT %moe.3 = f32[8]{0} custom-call(%x.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(M)/block_0/moe/cond/branch_1_fun/moe/pallas_call"}
}

ENTRY %main.1 (arg: f32[8]) -> f32[8] {
  %arg = f32[8]{0:T(128)} parameter(0), metadata={op_name="state.params['w']"}
  %fusion.8 = (f32[8]{0:T(128)S(1)}, f32[8]{0}) fusion(%arg, %arg), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/transpose(jvp(M))/block_0/mlp/up/dot_general"}
  %fusion.85 = f32[8]{0} fusion(%arg), kind=kLoop, calls=%fused_computation.1
  %copy-start.2 = (f32[8]{0}, f32[8]{0:S(1)}, u32[]) copy-start(%arg)
  %copy-done.2 = f32[8]{0:S(1)} copy-done(%copy-start.2)
  %tuple.20 = (s32[], f32[8]{0}) tuple(%arg, %arg)
  %while.4 = (s32[], f32[8]{0}) while(%tuple.20), condition=%cond.1, body=%body.1, metadata={op_name="jit(step)/jvp(M)/block_0/attn/while"}
  %conditional.5 = f32[8]{0} conditional(%arg, %arg, %arg), branch_computations={%branch_a, %branch_b}, metadata={op_name="jit(step)/jvp(M)/block_0/moe/cond"}
  %all-reduce.6 = f32[8]{0} all-reduce(%arg), to_apply=%region_0.1, metadata={op_name="jit(step)/transpose(jvp(M))/head/psum"}
  ROOT %reduce.7 = f32[8]{0} reduce(%arg, %arg), dimensions={0}, to_apply=%region_0.1, metadata={op_name="jit(step)/jvp(M)/head/reduce_sum"}
}
'''


def test_parse_hlo_scopes_on_a_literal_module():
    table = scopes.parse_hlo_scopes(HLO)
    assert table['module'] == 'jit_step'
    rows = table['instructions']
    assert set(rows) == {
        'fusion.8', 'fusion.85', 'copy-start.2', 'copy-done.2', 'while.4',
        'exp.6', 'copy.7', 'conditional.5', 'tanh.1', 'moe.3', 'all-reduce.6',
        'reduce.7'}
    assert rows['fusion.8'] == {
        'opcode': 'fusion', 'result': 'f32[8]', 'part': 'ffn.dense',
        'pass': 'backward', 'path': 'M/block_0/mlp/up/dot_general',
        'parts_fused': ['ffn.dense', 'optimizer']}
    # no scope of its own: its root's (the last instruction that has one)
    assert (rows['fusion.85']['part'], rows['fusion.85']['parts_fused']) == (
        'embed', ['embed'])
    for name in ('copy-start.2', 'copy-done.2'):
        assert (rows[name]['part'], rows[name]['pass'],
                rows[name]['path']) == ('unscoped', None, '')
    assert rows['while.4']['opcode'] == 'while' \
        and rows['while.4']['part'] == 'mixer'
    assert rows['exp.6']['part'] == 'mixer'
    # the compiler's copy inside the loop belongs to the loop's part
    assert (rows['copy.7']['part'], rows['copy.7']['path']) == (
        'mixer', 'M/block_0/attn')
    assert rows['conditional.5']['opcode'] == 'conditional'
    assert rows['tanh.1']['path'] == 'M/block_0/moe/every_token/tanh'
    assert rows['moe.3'] == {
        'opcode': 'custom-call', 'result': 'f32[8]', 'part': 'ffn.routed',
        'pass': 'forward', 'path': 'M/block_0/moe/pallas_call',
        'parts_fused': None}
    assert (rows['all-reduce.6']['part'], rows['all-reduce.6']['pass']) == (
        'collective', 'backward')
    assert rows['reduce.7']['part'] == 'head'


# -- one case a model family -----------------------------------------------------------

def _load(path):
    spec = importlib.util.spec_from_file_location(
        'op_scopes_' + re.sub(r'\W', '_', os.path.basename(path)), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _smaller(name, cfg):
    """The benchmark's tiny configurations, cut once more: a test of names
    needs every kind of layer once."""
    a = cfg['assumed']
    if name == 'tiny-hybrid':
        cfg.update(num_hidden_layers=2,
                   layer_types=['linear_attention', 'full_attention'])
        a.update(sequence_length=32, rows_per_chip_per_step=1)
    elif name == 'tiny-xing4':
        cfg.update(num_hidden_layers=2, hc_sinkhorn_iters=2)
        a.update(sequence_length=32, rows_per_chip_per_step=1)
    elif name == 'tiny-ling3':
        cfg.update(num_hidden_layers=3, layer_group_size=3)
        a.update(sequence_length=32, rows_per_chip_per_step=1)
    elif name == 'tiny-nemotron3':
        cfg.update(num_hidden_layers=3, hybrid_override_pattern='M*E')
        a.update(sequence_length=32, rows_per_chip_per_step=1)
    elif name == 'tiny-solar2':
        cfg.update(num_hidden_layers=2, gqa_layers=[0])
        a.update(sequence_length=32, rows_per_chip_per_step=1)
    elif name == 'tiny-gpt2':
        a.update(sequence_length=32, rows_per_chip_per_step=2)
    return cfg


FAMILIES = {
    'TransformerLM': ('tiny-gpt2', {'embed', 'mixer', 'ffn.dense', 'head',
                                    'loss', 'optimizer'}),
    'HybridLM': ('tiny-hybrid', {'embed', 'mixer', 'ffn.dense', 'head',
                                 'loss', 'optimizer'}),
    'LatentMoELM': ('tiny-xing4', {'embed', 'mixer', 'ffn.dense',
                                   'ffn.routed', 'ffn.shared', 'streams',
                                   'head', 'loss', 'optimizer'}),
    'LingHybridLM': ('tiny-ling3', {'embed', 'mixer', 'ffn.dense',
                                    'ffn.routed', 'ffn.shared', 'head',
                                    'loss', 'optimizer'}),
    'NemotronHLM': ('tiny-nemotron3', {'embed', 'mixer', 'ffn.routed',
                                       'ffn.shared', 'head', 'loss',
                                       'optimizer'}),
    'ResNet': ('tiny-resnet', {'body', 'norm', 'head', 'loss', 'optimizer'}),
    # LingHybridLM laid out as Solar-Open2: gated grouped-query attention and
    # Kimi delta attention on the rule's exact path
    'SolarHybrid': ('tiny-solar2', {'embed', 'mixer', 'ffn.routed',
                                    'ffn.shared', 'head', 'loss',
                                    'optimizer'}),
}
_family_tables = {}


def family_table(family):
    """The table of one step of the family's tiny benchmark configuration
    through its own program file (Pallas in interpret mode), made once."""
    if family in _family_tables:
        return _family_tables[family]
    name, _ = FAMILIES[family]
    cfg = _smaller(name, json.load(open(os.path.join(TINY, name + '.json'))))
    ref = _load(os.path.join(TINY, cfg['reference_file']))
    program = _load(os.path.join(TINY, cfg['program_file']))
    mine = trace.Tracer()
    previous = trace.set_global_tracer(mine)
    try:
        state, step = program.build(cfg, ref.init_params(cfg, 0),
                                    ref.init_batch_stats(cfg), None,
                                    interpret=True)
        rows = cfg['assumed']['rows_per_chip_per_step']
        if family == 'ResNet':
            batch = collections.namedtuple('Batch', 'image label')(
                jnp.zeros((rows, cfg['image_size'], cfg['image_size'],
                           cfg['channels']), jnp.uint8),
                jnp.zeros((rows,), jnp.int32))
        else:
            batch = collections.namedtuple('Batch', 'tokens')(
                jnp.arange(rows * (cfg['assumed']['sequence_length'] + 1),
                           dtype=jnp.int32).reshape(rows, -1)
                % cfg['vocab_size'])
        _, metrics = step(state, batch)
        assert np.isfinite(float(metrics['loss']))
        table, = mine.op_scopes().values()
    finally:
        trace.set_global_tracer(previous)
    _family_tables[family] = table['instructions']
    return _family_tables[family]


@pytest.mark.parametrize('family', sorted(FAMILIES))
def test_a_family_s_step_is_scoped(family):
    rows = family_table(family).values()
    counts = collections.Counter(r['part'] for r in rows)
    assert counts['other'] < 0.05 * len(rows), counts
    assert set(counts) >= FAMILIES[family][1], counts
    passes = {r['pass'] for r in rows}
    assert passes >= {'forward', 'backward', 'update'}
    if family not in ('TransformerLM', 'ResNet'):       # blocks recomputed
        assert 'recompute' in passes
    # the Pallas calls (interpreted here: loops and choices) keep the
    # innermost scopes the kernel metrics find them by
    scoped = ' '.join(r['path'] for r in rows)
    for scope in {'TransformerLM': ['attn'], 'HybridLM': ['attn', 'gdn'],
                  'LatentMoELM': ['attn', 'moe', 'token_sums'],
                  'LingHybridLM': ['attn', 'kda', 'moe', 'token_sums'],
                  'NemotronHLM': ['attn', 'ssd', 'moe', 'token_sums'],
                  'SolarHybrid': ['attn', 'kda_exact', 'moe', 'token_sums'],
                  'ResNet': []}[family]:
        assert re.search(r'/{}/(pallas_call|[a-z_]+)( |$)'.format(scope),
                         scoped), scope


def test_every_name_the_rules_know_is_met():
    """Every alternative of every rule names something some family's step
    has: a rule for a name nobody uses is a rule to take out. ``hc`` is the
    scope of the stream kernels, which want streams 128 lanes wide (the tiny
    models mix their streams in ``jax.numpy``)."""
    names = set()
    for family in FAMILIES:
        for row in family_table(family).values():
            names.update(row['path'].split('/'))
    missing = []
    for pattern, part in scopes.PART_RULES:
        inner = pattern.pattern[len('(?:^|/)(?:'):-len(')(?:/|$)')]
        for alternative in inner.split('|'):
            if not any(re.fullmatch(alternative, name) for name in names):
                missing.append((part, alternative))
    assert missing == [('streams', 'hc')]


def test_what_solar_s_layers_add_lands_in_named_parts():
    """The exact rule's kernels, the low-rank decay and output gates, the
    attention's output gate and the routing over the published experts each
    land in a part of the model's own, not in ``unscoped`` or ``other``."""
    rows = family_table('SolarHybrid').values()
    for scope, part in (('kda_exact', 'mixer'), ('f_a_proj', 'mixer'),
                        ('f_b_proj', 'mixer'), ('g_a_proj', 'mixer'),
                        ('g_b_proj', 'mixer'), ('gqa_gate', 'mixer'),
                        ('router', 'ffn.routed'), ('routing', 'ffn.routed')):
        mine = [r for r in rows if scope in r['path'].split('/')]
        assert mine, scope
        assert {r['part'] for r in mine} == {part}, (scope, mine[:3])
