"""Plain reference of the Nemotron-3-Super configuration: Mamba-2 state-space
layers, latent relu² mixtures of experts and grouped-query attention in
blocks of one sub-layer each; loss, gradients and the AdamW update in
straightforward ``jax.numpy`` at float32 and ``Precision.HIGHEST``. The
state-space rule is a ``lax.scan`` over tokens, as it is written, in segments
of 64 that the backward pass recomputes; attention is dense and causal by
blocks of queries; the experts are a loop over the experts held with a mask
each; no flax, nothing of ``petastorm_tpu``. It also makes the weights (from
the seed) and counts operations and bytes (from the shapes).

``d`` = ``hidden_size`` 4096, ``x [T, d]``. Block ``i`` (pre-norm, one
residual stream, RMSNorm with ``layer_norm_epsilon`` 1e-5, no bias but the
convolution's), by letter ``i`` of ``hybrid_override_pattern``::

    x <- x + f_i(rmsnorm(x))

**M, Mamba-2** (``mamba_num_heads`` heads of ``mamba_head_dim`` 64 in
``n_groups`` groups, a state ``ssm_state_size`` 128 wide)::

    [z | x | B | C | dt] = x W_in                 (in_proj's column blocks)
    [x | B | C] = silu(conv4([x | B | C]) + bias)  (depthwise, causal)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)  (a scalar a head each)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
    y = rmsnorm_by_groups(y * silu(z)) W_out      (a group's heads, 1,024 wide)

head ``h`` reads the ``B`` and ``C`` of group ``h // (heads / groups)``.

**\\*, grouped-query attention**: ``num_attention_heads`` query heads and
``num_key_value_heads`` KV heads of ``head_dim`` 128, a KV head shared by
``heads / kv_heads`` query heads, causal softmax at ``128^-1/2``, no rotary
positions (``rope_theta`` is in the config; Nemotron-H's attention applies
none: assumed), ``W_o``.

**E, latent mixture of experts**: ``s = sigmoid(W_r x)`` over all 512
published experts in float32 on the hidden state; the top 22 (``n_group``
1) by ``s + b`` (``b`` zeros and constant: assumed), weights ``s_e /
sum_picked s`` (``norm_topk_prob``) times ``routed_scaling_factor`` 5;
``u = x W_down`` (4096 -> 1024); ``y = (sum over e picked and held of w_e
relu(u W_up_e)^2 W_down_e) W_up + relu(x W_1)^2 W_2``: every routed expert
1024 -> 2688 -> 1024 with no gate, summed in the latent space, then the one
up-projection; the shared expert 4096 -> 5376 -> 4096 on the hidden state.

Final rmsnorm, an untied head, mean cross-entropy against the next token.

The share: ``mamba_num_heads``, ``n_groups``, ``num_attention_heads``,
``num_key_value_heads`` and ``n_routed_experts`` in ``cfg`` count what is
held here (the router stays ``published.n_routed_experts`` wide and
``assumed.experts_held`` names the experts), ``vocab_size`` the vocabulary's
rows held; what absent chips would add is left out, as in the program.

The tree it makes has the layout the program's flax module reads
(``block_<i>/mixer/x_proj`` ...): names, not values. ``quant`` is the
control's hook (``lowprec.Rounding``): it rounds both operands of every
product the program hands the MXU in bfloat16 outside the rule (every
projection's, expert's and attention's) and the gradient that comes back into
it; the router's product and the recurrence stay float32.
"""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 1024      # rows of attention scores held at once
SCAN_SEGMENT = 64       # tokens between two kept states of the recurrence


def _sizes(cfg):
    return dict(
        d=cfg['hidden_size'], v=cfg['vocab_size'],
        mh=cfg['mamba_num_heads'], mg=cfg['n_groups'],
        mp=cfg['mamba_head_dim'], n=cfg['ssm_state_size'],
        taps=cfg['conv_kernel'], h=cfg['num_attention_heads'],
        kv=cfg['num_key_value_heads'], hd=cfg['head_dim'],
        fe=cfg['moe_intermediate_size'],
        fs=cfg['n_shared_experts']
        * cfg['moe_shared_expert_intermediate_size'],
        latent=cfg['moe_latent_size'], held=cfg['n_routed_experts'],
        experts=cfg['published']['n_routed_experts'])


KINDS = {'M': 'mamba', '*': 'attention', 'E': 'moe'}


def layer_kinds(cfg):
    """Every block's kind by its letter of the pattern: ``'mamba'`` (``M``),
    ``'attention'`` (``*``), ``'moe'`` (``E``)."""
    pattern = cfg['hybrid_override_pattern']
    if len(pattern) != cfg['num_hidden_layers']:
        raise ValueError('a pattern of {} blocks for {} layers'.format(
            len(pattern), cfg['num_hidden_layers']))
    return [KINDS[letter] for letter in pattern]


def _block_shapes(b, kind, s):
    d = s['d']
    shapes = {(b, 'norm', 'scale'): (d,)}
    if kind == 'mamba':
        m = (b, 'mixer')
        inner, width = s['mh'] * s['mp'], s['mg'] * s['n']
        for name, features in (('z', inner), ('x', inner), ('b', width),
                               ('c', width), ('dt', s['mh'])):
            shapes[m + (name + '_proj', 'kernel')] = (d, features)
        for name, features in (('x', inner), ('b', width), ('c', width)):
            shapes[m + ('conv_' + name,)] = (s['taps'], features)
            shapes[m + ('conv_' + name + '_bias',)] = (features,)
        for name in ('dt_bias', 'A_log', 'D'):
            shapes[m + (name,)] = (s['mh'],)
        shapes[m + ('norm', 'scale')] = (inner,)
        shapes[m + ('out_proj', 'kernel')] = (inner, d)
    elif kind == 'attention':
        a = (b, 'attn')
        shapes[a + ('q_proj', 'kernel')] = (d, s['h'], s['hd'])
        shapes[a + ('k_proj', 'kernel')] = (d, s['kv'], s['hd'])
        shapes[a + ('v_proj', 'kernel')] = (d, s['kv'], s['hd'])
        shapes[a + ('o_proj', 'kernel')] = (s['h'], s['hd'], d)
    else:
        m, latent = (b, 'moe'), s['latent']
        shapes[m + ('router', 'kernel')] = (d, s['experts'])
        shapes[m + ('latent_down', 'kernel')] = (d, latent)
        shapes[m + ('latent_up', 'kernel')] = (latent, d)
        shapes[m + ('shared', 'up', 'kernel')] = (d, s['fs'])
        shapes[m + ('shared', 'down', 'kernel')] = (s['fs'], d)
        shapes[m + ('experts_up',)] = (s['held'], latent, s['fe'])
        shapes[m + ('experts_down',)] = (s['held'], s['fe'], latent)
    return shapes


def param_shapes(cfg):
    s = _sizes(cfg)
    d, v = s['d'], s['v']
    shapes = {('embed', 'embedding'): (v, d), ('final_norm', 'scale'): (d,),
              ('head', 'kernel'): (d, v)}
    for i, kind in enumerate(layer_kinds(cfg)):
        shapes.update(_block_shapes('block_{}'.format(i), kind, s))
    return shapes


def _nest(flat):
    tree = {}
    for path, value in flat.items():
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = value
    return tree


def seed_key(seed):
    """A key from any whole number, also one past 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)),
                              seed // (2 ** 31 - 1))


def init_params(cfg, seed):
    """All weights in one jitted call on the device, float32 (json,
    ``assumed.init``)."""
    shapes = param_shapes(cfg)
    bound = 1.0 / np.sqrt(cfg['conv_kernel'])
    lo, hi, floor = (cfg['time_step_min'], cfg['time_step_max'],
                     cfg['time_step_floor'])
    rescale = 1.0 / np.sqrt(cfg['published']['num_hidden_layers']) \
        if cfg['rescale_prenorm_residual'] else 1.0

    @jax.jit
    def make(key):
        flat = {}
        for i, (path, shape) in enumerate(sorted(shapes.items())):
            k = jax.random.fold_in(key, i)
            last = path[-1]
            if last == 'embedding':
                flat[path] = jax.random.normal(k, shape, jnp.float32)
            elif last == 'kernel' or last.startswith('experts_'):
                scale = rescale if path[-2] == 'out_proj' else 1.0
                flat[path] = 0.02 * scale * jax.random.normal(k, shape,
                                                              jnp.float32)
            elif last.startswith('conv_'):
                flat[path] = jax.random.uniform(k, shape, jnp.float32,
                                                -bound, bound)
            elif last == 'A_log':
                flat[path] = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                                        1.0, 16.0))
            elif last == 'dt_bias':
                dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                                np.log(lo), np.log(hi)))
                dt = jnp.maximum(dt, floor)
                flat[path] = dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1
            else:                                           # D, norm scales
                flat[path] = jnp.ones(shape, jnp.float32)
        return _nest(flat)

    return make(seed_key(seed))


def init_batch_stats(cfg):
    return None


# -- forward -----------------------------------------------------------------

def _mm(spec, a, b, quant):
    if quant is not None:
        a, b = quant.operand(a), quant.operand(b)
    y = jnp.einsum(spec, a, b, precision=HIGHEST)
    return y if quant is None else quant.cotangent(y)


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * scale


def _conv_silu(x, kernel, bias):
    """Depthwise causal convolution along the sequence plus a bias, then
    SiLU: ``x [B, T, C]``, ``kernel [taps, C]``; position ``t`` sees ``t -
    taps + 1 .. t``, zeros before the row's start."""
    taps, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, i:i + t] * kernel[i]
                           for i in range(taps)) + bias)


def recurrence(x, b, c, dt, a):
    """The rule token by token. ``x [B, T, G, K, P]`` (``K`` heads a group),
    ``b, c [B, T, G, N]``, ``dt [B, T, G, K]``, ``a [G, K]`` -> ``y [B, T, G,
    K, P]`` (without the ``D`` skip)."""
    bsz, t, g, k, p = x.shape
    n = b.shape[-1]

    def token(s, xs):
        x_t, b_t, c_t, dt_t = xs
        s = s * jnp.exp(dt_t * a)[..., None, None] + jnp.einsum(
            'bgkp,bgn->bgkpn', dt_t[..., None] * x_t, b_t, precision=HIGHEST)
        return s, jnp.einsum('bgkpn,bgn->bgkp', s, c_t, precision=HIGHEST)

    segment = SCAN_SEGMENT if t % SCAN_SEGMENT == 0 else t

    @jax.checkpoint
    def tokens(s, xs):
        return lax.scan(token, s, xs)

    def split(v):
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape((t // segment, segment) + v.shape[1:])

    xs = tuple(split(v) for v in (x, b, c, dt))
    _, y = lax.scan(tokens, jnp.zeros((bsz, g, k, p, n), jnp.float32), xs)
    return jnp.moveaxis(y.reshape((t,) + y.shape[2:]), 0, 1)


def _mamba(p, x, cfg, quant):
    s = _sizes(cfg)
    bsz, t, _ = x.shape
    g, k = s['mg'], s['mh'] // s['mg']

    def proj(name):
        return _mm('btd,df->btf', x, p[name + '_proj']['kernel'], quant)

    def conv(name):
        return _conv_silu(proj(name), p['conv_' + name],
                          p['conv_' + name + '_bias'])

    z, xs, b, c = proj('z'), conv('x'), conv('b'), conv('c')
    dt = jax.nn.softplus(proj('dt') + p['dt_bias'])
    a = -jnp.exp(p['A_log'])
    y = recurrence(xs.reshape(bsz, t, g, k, s['mp']),
                   b.reshape(bsz, t, g, s['n']), c.reshape(bsz, t, g, s['n']),
                   dt.reshape(bsz, t, g, k), a.reshape(g, k))
    y = y.reshape(xs.shape) + jnp.repeat(p['D'], s['mp']) * xs
    gated = (y * jax.nn.silu(z)).reshape(bsz, t, g, -1)
    y = _rms(gated, 1.0, cfg['layer_norm_epsilon']).reshape(xs.shape) \
        * p['norm']['scale']
    return _mm('btf,fd->btd', y, p['out_proj']['kernel'], quant)


def _attend(q, k, v, first, scale, quant):
    """Queries ``first .. first + rows`` against every key up to each."""
    scores = _mm('bqhk,bshk->bhqs', q, k, quant) * scale
    mask = (first + jnp.arange(q.shape[1]))[:, None] >= jnp.arange(
        k.shape[1])[None, :]
    probs = jax.nn.softmax(jnp.where(mask[None, None], scores, -jnp.inf),
                           axis=-1)
    return _mm('bhqs,bshk->bqhk', probs, v, quant)


def _attention(p, x, cfg, quant):
    b, t, _ = x.shape
    h, kv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    q = _mm('btd,dhk->bthk', x, p['q_proj']['kernel'], quant)
    k, v = (jnp.repeat(_mm('btd,dhk->bthk', x, p[name]['kernel'], quant),
                       h // kv, axis=2) for name in ('k_proj', 'v_proj'))
    rows = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    blocks = jnp.moveaxis(q.reshape((b, t // rows, rows) + q.shape[2:]), 1, 0)
    out = lax.map(lambda xs: jax.checkpoint(functools.partial(
        _attend, scale=cfg['head_dim'] ** -0.5, quant=quant))(
            xs[0], k, v, xs[1]), (blocks, rows * jnp.arange(t // rows)))
    out = jnp.moveaxis(out, 0, 1).reshape(q.shape)
    return _mm('bthk,hkd->btd', out, p['o_proj']['kernel'], quant)


def _relu2(x, up, down, quant):
    hidden = jnp.square(jax.nn.relu(_mm('btd,df->btf', x, up, quant)))
    return _mm('btf,fd->btd', hidden, down, quant)


def route(p, x, cfg):
    """``(experts [B, T, k], weights [B, T, k])``: the published experts each
    token goes to and what each one's output is weighted by."""
    scores = jax.nn.sigmoid(jnp.einsum('btd,de->bte', x, p['router']['kernel'],
                                       precision=HIGHEST))
    _, experts = lax.top_k(scores, cfg['num_experts_per_tok'])   # b: zeros
    picked = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg['norm_topk_prob']:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return experts, picked * cfg['routed_scaling_factor']


def _experts(p, x, cfg, quant):
    sh = p['shared']
    y = _relu2(x, sh['up']['kernel'], sh['down']['kernel'], quant)
    experts, weights = route(p, x, cfg)
    u = _mm('btd,dl->btl', x, p['latent_down']['kernel'], quant)
    routed = 0.0
    for slot, expert in enumerate(cfg['assumed']['experts_held']):
        mine = jnp.sum(jnp.where(experts == expert, weights, 0.0), axis=-1)
        routed = routed + mine[..., None] * _relu2(
            u, p['experts_up'][slot], p['experts_down'][slot], quant)
    return y + _mm('btl,ld->btd', routed, p['latent_up']['kernel'], quant)


def _block(p, x, kind, cfg, quant):
    inner = _rms(x, p['norm']['scale'], cfg['layer_norm_epsilon'])
    if kind == 'mamba':
        return x + _mamba(p['mixer'], inner, cfg, quant)
    if kind == 'attention':
        return x + _attention(p['attn'], inner, cfg, quant)
    return x + _experts(p['moe'], inner, cfg, quant)


def logits(params, tokens, cfg, quant=None):
    """``tokens`` int32 [B, T] -> float32 logits [B, T, rows held]."""
    x = params['embed']['embedding'][tokens]
    for i, kind in enumerate(layer_kinds(cfg)):
        x = jax.checkpoint(functools.partial(
            _block, kind=kind, cfg=cfg, quant=quant))(
                params['block_{}'.format(i)], x)
    x = _rms(x, params['final_norm']['scale'], cfg['layer_norm_epsilon'])
    return _mm('btd,dv->btv', x, params['head']['kernel'], quant)


def _loss(params, tokens, cfg, quant, positions):
    """Mean next-token cross-entropy over the first ``positions`` positions
    of rows of ``T + 1`` tokens."""
    z = logits(params, tokens[:, :-1], cfg, quant)
    logp = z - jax.scipy.special.logsumexp(z, axis=-1, keepdims=True)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(picked[:, :positions])


def loss(params, inputs, cfg, quant=None):
    tokens = inputs['tokens']
    return _loss(params, tokens, cfg, quant, tokens.shape[1] - 1)


@functools.lru_cache(maxsize=None)
def _compiled(frozen_cfg, quant, positions):
    cfg = json.loads(frozen_cfg)
    return jax.jit(jax.value_and_grad(
        lambda p, t: _loss(p, t, cfg, quant, positions)))


@jax.jit
def _accumulate(acc, grads):
    return jax.tree_util.tree_map(jnp.add, acc, grads)


def loss_and_grad(params, inputs, cfg, quant=None, rows_used=None):
    """One row at a time, each row's loss and gradient a mean over its own
    positions, then the mean over rows. ``rows_used`` (a fault for the tests
    and the calibration): only that many leading rows enter the mean; where a
    step is one row, half of a step is the first half of the row's positions,
    which is what ``rows_used`` 0 takes."""
    tokens = inputs['tokens']
    positions = tokens.shape[1] - 1
    if rows_used is not None:
        if rows_used < 1:
            positions //= 2
        else:
            tokens = tokens[:rows_used]
    fn = _compiled(json.dumps(cfg, sort_keys=True), quant, positions)
    total, acc = 0.0, None
    for start in range(tokens.shape[0]):
        value, grads = fn(params, tokens[start:start + 1])
        total = total + value
        acc = grads if acc is None else _accumulate(acc, grads)
    count = tokens.shape[0]
    return total / count, jax.tree_util.tree_map(lambda g: g / count, acc)


# -- optimizer: AdamW, its moments on the host between steps --------------------

def opt_init(params, cfg):
    """``None``: zero moments, made when the first step needs them."""
    return {'mu': None, 'nu': None}


def gradient_as_optimizer_gets_it(grads, params, cfg):
    """AdamW's first moment sees the bare gradient: decay is added after the
    moments, so ``mu_1 / (1 - b1)`` is the gradient itself."""
    return grads


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9))
def _adamw_leaf(p, m, n, g, step, lr, b1, b2, eps, wd):
    m = b1 * m + (1 - b1) * g
    n = b2 * n + (1 - b2) * g * g
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    return p - lr * ((m / c1) / (jnp.sqrt(n / c2) + eps) + wd * p), m, n


def opt_apply(params, opt, grads, cfg, step):
    """``step`` counts from 1. A leaf's two moments come to the chip, move
    and go back to the host, one leaf after another."""
    a = cfg['assumed']
    leaves, tree = jax.tree_util.tree_flatten(params)
    g_leaves = jax.tree_util.tree_leaves(grads)
    mu = opt['mu'] or [np.zeros(p.shape, np.float32) for p in leaves]
    nu = opt['nu'] or [np.zeros(p.shape, np.float32) for p in leaves]
    moved = []
    for i, (p, g) in enumerate(zip(leaves, g_leaves)):
        new, m, n = _adamw_leaf(p, mu[i], nu[i], g, jnp.float32(step),
                                a['learning_rate'], a['b1'], a['b2'],
                                a['eps'], a['weight_decay'])
        moved.append(new)
        mu[i], nu[i] = np.asarray(m), np.asarray(n)
    return jax.tree_util.tree_unflatten(tree, moved), {'mu': mu, 'nu': nu}


# -- operations and bytes, from the shapes ---------------------------------------

def expected_pairs_per_row(cfg):
    """(token, expert) pairs an expert layer here is sent from one row, in
    expectation over uniform routing: the held share of ``T * top_k``."""
    return cfg['assumed']['sequence_length'] * cfg['num_experts_per_tok'] \
        * cfg['n_routed_experts'] // cfg['published']['n_routed_experts']


def _chunks_per_row(cfg):
    return -(-cfg['assumed']['sequence_length'] // cfg['chunk_size'])


def _ssd_forward_flops_per_chunk(cfg):
    """The chunked rule's forward on one chunk of ``L`` tokens of one layer
    (every head and group held), 2 operations a multiply-add, a product under
    the causal mask counted by the half that is kept: ``C B^T`` once a group
    (``L L N``), and a head's ``(C B^T * E)(dt x)`` (``L L P``), its read of
    the state ``C h`` and its write ``B^T (x w)`` (``2 L N P`` each)."""
    s, c = _sizes(cfg), cfg['chunk_size']
    return s['mg'] * c * c * s['n'] \
        + s['mh'] * (c * c * s['mp'] + 4 * c * s['n'] * s['mp'])


def _ssd_work_per_chunk(cfg):
    """``(operations, bytes)`` of the whole rule on one chunk of one layer,
    forward once and in reverse once: what runs under the name ``ssd``.

    In reverse, besides what the kernel computes again of the forward pass:
    a head's ``P^T dy`` and ``dy (dt x)^T`` under the mask (``L L P`` each),
    ``q h^T``, ``C^T q``, ``B G`` and ``(x w) G^T`` (``2 L N P`` each); a
    group's ``d(C B^T) B`` and ``d(C B^T)^T C`` under the mask (``L L N``
    each).

    Bytes: forward reads ``x``, ``B``, ``C`` (bfloat16) and ``dt`` (float32),
    writes ``y`` and the state each chunk starts from (bfloat16); the reverse
    pass reads ``dy``, ``x``, ``B``, ``C``, ``dt`` and the state and writes
    ``dx`` (bfloat16), ``dB``, ``dC`` and ``d(dt)`` (float32)."""
    s, c = _sizes(cfg), cfg['chunk_size']
    heads, groups, p, n = s['mh'], s['mg'], s['mp'], s['n']
    reverse = groups * 2 * c * c * n \
        + heads * (2 * c * c * p + 8 * c * n * p)
    forward_bytes = heads * (c * p * 2 + c * 4 + c * p * 2 + n * p * 2) \
        + groups * 2 * c * n * 2
    reverse_bytes = heads * (2 * c * p * 2 + c * 4 + n * p * 2
                             + c * p * 2 + c * 4) \
        + groups * 2 * c * n * (2 + 4)
    return (_ssd_forward_flops_per_chunk(cfg) + reverse,
            forward_bytes + reverse_bytes)


def _mamba_flops(cfg):
    """One Mamba-2 mixer's forward on one row: the in- and out-projections
    and the rule."""
    s, t = _sizes(cfg), cfg['assumed']['sequence_length']
    inner = s['mh'] * s['mp']
    projections = s['d'] * (3 * inner + 2 * s['mg'] * s['n'] + s['mh'])
    return t * 2 * projections \
        + _chunks_per_row(cfg) * _ssd_forward_flops_per_chunk(cfg)


def _attention_flops(cfg):
    """One attention mixer's forward on one row: the four projections, and a
    causal head's two products by the half that is kept (``T T hd`` each)."""
    s, t = _sizes(cfg), cfg['assumed']['sequence_length']
    weights = s['d'] * s['hd'] * (2 * s['h'] + 2 * s['kv'])
    return t * 2 * weights + s['h'] * t * t * 2 * s['hd']


def _moe_flops(cfg):
    """One expert layer's forward on one row: the router, the latent down-
    and up-projections, the shared expert, and the routed experts' two
    products at their expected pairs."""
    s, t = _sizes(cfg), cfg['assumed']['sequence_length']
    d = s['d']
    return t * 2 * (d * s['experts'] + 2 * d * s['latent'] + 2 * d * s['fs']) \
        + expected_pairs_per_row(cfg) * 2 * 2 * s['latent'] * s['fe']


def forward_flops_per_row(cfg):
    """A row is one sequence of ``sequence_length`` positions; what is held
    here only; the routed experts at their expected pairs. Matrix products: 2
    operations a multiply-add."""
    s, t = _sizes(cfg), cfg['assumed']['sequence_length']
    total = t * 2 * s['d'] * s['v']
    per_kind = {'mamba': _mamba_flops(cfg), 'attention': _attention_flops(cfg),
                'moe': _moe_flops(cfg)}
    return total + sum(per_kind[kind] for kind in layer_kinds(cfg))


def train_flops_per_row(cfg):
    """Forward and backward; what recomputation runs again is not counted."""
    return 3 * forward_flops_per_row(cfg)


def kernels(cfg, rows_per_chip, moe_pairs_per_step=None):
    """The kernels' work in one train step on one chip.

    ``ssd``: events named ``ssd*`` in the device trace, the two Pallas calls
    of a Mamba-2 layer (``ops.ssd``), which hold the whole rule: operations
    and bytes from :func:`_ssd_work_per_chunk`, forward once and in reverse
    once. The time they are set against holds the recomputed forward pass
    too, which the count leaves out.

    ``moe``: events named ``moe*``, the grouped products of the experts held:
    for the pairs routed to them in a step, summed over the expert layers
    (``moe_pairs_per_step``; ``None``: the expectation, 352 an expert a layer
    a row), the two products forward, the same again where the block is
    recomputed, and their four gradient products; every array once in
    bfloat16 a product.

    ``flash``: events named ``attn*``: forward two products, backward five,
    ``2 T T hd`` each, halved by the mask; q, k, v, o and their gradients
    once each in bfloat16 (the KV heads as the kernel reads them, repeated
    to the query heads)."""
    s, t = _sizes(cfg), cfg['assumed']['sequence_length']
    kinds = layer_kinds(cfg)
    chunks = kinds.count('mamba') * rows_per_chip * _chunks_per_row(cfg)
    flops, moved = _ssd_work_per_chunk(cfg)
    ssd = {'match': '^ssd', 'flops': chunks * flops, 'bytes': chunks * moved}
    latent, fe, held = s['latent'], s['fe'], s['held']
    layers = kinds.count('moe') * rows_per_chip
    pairs = layers * expected_pairs_per_row(cfg) \
        if moe_pairs_per_step is None else moe_pairs_per_step
    passes = 2 if cfg['assumed']['recompute_each_layer'] else 1
    product = pairs * 2 * 2 * latent * fe               # one forward
    rows_moved = 2 * pairs * (latent + fe)              # in and out, both
    weights = layers * held * 2 * latent * fe
    moe = {'match': '^moe',
           'flops': (passes + 2) * product,
           'bytes': (passes + 2) * 2 * (rows_moved + weights)}
    blocks = kinds.count('attention') * rows_per_chip
    hd = s['hd']
    flash = {'match': '^attn',
             'flops': blocks * s['h'] * 7 * hd * t * t,
             'bytes': blocks * s['h'] * t * 8 * hd * 2}
    return {'ssd': ssd, 'moe': moe, 'flash': flash}
