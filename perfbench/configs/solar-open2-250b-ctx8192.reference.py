"""Plain reference of the Solar-Open2-250B configuration: Kimi delta attention
(a delta rule whose decay is a vector a head and token, with no lower bound,
and write strengths in (0, 2)) in three layers of four, gated grouped-query
attention with no positions in the fourth, top-8 routing over 320 experts with
a shared one in every layer; loss, gradients and the AdamW update in
straightforward ``jax.numpy`` at float32 and ``Precision.HIGHEST``. The rule
is a ``lax.scan`` over tokens, as it is written, in segments of 64 that the
backward pass recomputes: a token's decay is ``exp(g_t)`` with ``g_t <= 0``,
so no exponent is positive whatever the gate; attention is dense and causal
by blocks of queries; the experts are a loop over the experts held with a
mask each; no flax, nothing of ``petastorm_tpu``. It also makes the weights
(from the seed) and counts operations and bytes (from the shapes).

``d`` = ``hidden_size`` 4096, ``x [T, d]``. Layer ``i`` (pre-norm, one
residual stream, RMSNorm with ``rms_norm_eps`` 1e-5, no bias anywhere)::

    x <- x + mixer_i(rmsnorm(x));   x <- x + ffn_i(rmsnorm(x))

**Kimi delta attention** (``i`` not in ``gqa_layers``; Kimi Linear,
arXiv:2510.26692, section 3, with ``kda_use_full_proj`` false), per head,
``d_k = d_v = linear_attn_config.head_dim`` 128, a state ``S`` in
``R^{128 x 128}`` from zero::

    q = unit(silu(conv4(x W_q))) 128^-1/2;  k = unit(silu(conv4(x W_k)));  v = silu(conv4(x W_v))
    g_t = -exp(A_log) softplus(x_t W_fa W_fb + dt_bias)        in (-inf, 0)^128
    b_t = 2 sigmoid(x_t W_b)                                   in (0, 2)   (kda_allow_neg_eigval)
    S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T;   o_t = S_t^T q_t
    y = W_o [rmsnorm(o_h) * sigmoid(x W_ga W_gb)_h]

``conv4`` is a causal depthwise convolution of ``short_conv_kernel_size`` 4
taps with no bias; ``unit`` the L2 norm over the head (``a / sqrt(|a|^2 +
1e-6)``); ``W_fa``, ``W_ga`` are ``[d, 128]`` (the low rank is Kimi Linear's
``head_dim``: assumed), ``W_fb``, ``W_gb`` ``[128, heads x 128]``; ``A_log``
a scalar a head and ``dt_bias`` a vector a head (assumed, Kimi Linear's);
the output gate one a channel, the norm one a head with a shared scale of
128.

**Gated grouped-query attention** (``i`` in ``gqa_layers``, ``use_rope``
false: no positions)::

    q = x W_q (heads),  k = x W_k,  v = x W_v (KV heads, a KV head shared by heads / KV heads query heads)
    o = causal softmax(q k^T 128^-1/2) v;   y = W_o [o * sigmoid(x W_gate)]

the gate one a channel (``use_gqa_gate``; the granularity assumed, as
Qwen3-Next's gated attention).

**Feed-forward** in every layer (``first_k_dense_replace`` 0):
``shared(x) + sum over e picked and held of w_e expert_e(x)``, every expert
and the shared one a SwiGLU of ``moe_intermediate_size`` 1280. ``s =
sigmoid(W_r x)`` over all 320 published experts in float32 (assumed: the
config names no scoring function); the best ``num_experts_per_tok`` 8 of ``s
+ b`` (``b`` zeros and constant: assumed), no groups; weights ``s_e /
sum_picked s`` (``norm_topk_prob``) times ``routed_scaling_factor`` 1.

Final rmsnorm, an untied head, mean cross-entropy against the next token.

The share: ``n_routed_experts`` in ``cfg`` counts what is held here (the
router stays ``published.n_routed_experts`` wide and ``assumed.experts_held``
names the experts), ``num_attention_heads``, ``num_key_value_heads`` and
``linear_attn_config.num_heads`` the heads held, ``vocab_size`` the
vocabulary's rows held; what absent chips would add is left out, as in the
program.

The tree it makes has the layout the program's flax module reads
(``block_<i>/mixer/q_proj`` ...): names, not values. ``quant`` is the control's
hook (``lowprec.Rounding``): it rounds both operands of every product the
program hands the MXU in bfloat16 outside the rule (every projection's,
expert's and attention's) and the gradient that comes back into it; the
router's product and the recurrence stay float32.
"""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 512       # rows of attention scores held at once
LOSS_BLOCK = 1024       # rows of logits held at once
SCAN_SEGMENT = 64       # tokens between two kept states of the recurrence
UNIT_EPS = 1e-6


def _sizes(cfg):
    lin = cfg['linear_attn_config']
    return dict(
        d=cfg['hidden_size'], v=cfg['vocab_size'],
        h=cfg['num_attention_heads'], kv=cfg['num_key_value_heads'],
        hd=cfg['head_dim'], lh=lin['num_heads'], lhd=lin['head_dim'],
        taps=lin['short_conv_kernel_size'], rank=cfg['assumed']['low_rank'],
        fe=cfg['moe_intermediate_size'], held=cfg['n_routed_experts'],
        experts=cfg['published']['n_routed_experts'],
        fs=cfg['n_shared_experts'] * cfg['moe_intermediate_size'])


def layer_kinds(cfg):
    """``(mixer, feed-forward)`` of every layer: ``'gqa'`` for the layers of
    ``gqa_layers``, else ``'kda'``; ``'dense'`` for the first
    ``first_k_dense_replace`` layers, ``'moe'`` after."""
    return [('gqa' if i in cfg['gqa_layers'] else 'kda',
             'dense' if i < cfg['first_k_dense_replace'] else 'moe')
            for i in range(cfg['num_hidden_layers'])]


def _block_shapes(b, mixer, ffn, s):
    d = s['d']
    shapes = {(b, 'mixer_norm', 'scale'): (d,), (b, 'ffn_norm', 'scale'): (d,)}
    if mixer == 'kda':
        m, h, hd, r = (b, 'mixer'), s['lh'], s['lhd'], s['rank']
        for name in ('q', 'k', 'v'):
            shapes[m + (name + '_proj', 'kernel')] = (d, h, hd)
            shapes[m + ('conv_' + name,)] = (s['taps'], h, hd)
        for name in ('f', 'g'):
            shapes[m + (name + '_a_proj', 'kernel')] = (d, r)
            shapes[m + (name + '_b_proj', 'kernel')] = (r, h, hd)
        shapes[m + ('A_log',)] = (h,)
        shapes[m + ('dt_bias',)] = (h, hd)
        shapes[m + ('b_proj', 'kernel')] = (d, h)
        shapes[m + ('o_norm', 'scale')] = (hd,)
        shapes[m + ('o_proj', 'kernel')] = (h, hd, d)
    else:
        a, h, hd = (b, 'attn'), s['h'], s['hd']
        for name, heads in (('q', h), ('k', s['kv']), ('v', s['kv']),
                            ('gate', h)):
            shapes[a + (name + '_proj', 'kernel')] = (d, heads, hd)
        shapes[a + ('o_proj', 'kernel')] = (h, hd, d)
    if ffn == 'dense':
        raise ValueError('no dense layer: first_k_dense_replace is 0')
    m, fs = (b, 'moe'), s['fs']
    shapes[m + ('router', 'kernel')] = (d, s['experts'])
    for name, shape in (('gate', (d, fs)), ('up', (d, fs)),
                        ('down', (fs, d))):
        shapes[m + ('shared', name, 'kernel')] = shape
    shapes[m + ('experts_gate_up',)] = (s['held'], d, 2 * s['fe'])
    shapes[m + ('experts_down',)] = (s['held'], s['fe'], d)
    return shapes


def param_shapes(cfg):
    s = _sizes(cfg)
    d, v = s['d'], s['v']
    shapes = {('embed', 'embedding'): (v, d), ('final_norm', 'scale'): (d,),
              ('head', 'kernel'): (d, v)}
    for i, (mixer, ffn) in enumerate(layer_kinds(cfg)):
        shapes.update(_block_shapes('block_{}'.format(i), mixer, ffn, s))
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
    taps = cfg['linear_attn_config']['short_conv_kernel_size']

    @jax.jit
    def make(key):
        flat = {}
        for i, (path, shape) in enumerate(sorted(shapes.items())):
            k = jax.random.fold_in(key, i)
            last = path[-1]
            if last == 'embedding':
                flat[path] = jax.random.normal(k, shape, jnp.float32)
            elif last == 'kernel' or last.startswith('experts_'):
                flat[path] = 0.02 * jax.random.normal(k, shape, jnp.float32)
            elif last.startswith('conv_'):
                bound = 1.0 / np.sqrt(taps)
                flat[path] = jax.random.uniform(k, shape, jnp.float32,
                                                -bound, bound)
            elif last == 'A_log':
                flat[path] = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                                        1.0, 16.0))
            elif last == 'dt_bias':
                flat[path] = jnp.zeros(shape, jnp.float32)
            else:
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


def _conv_silu(x, kernel):
    """Depthwise causal convolution along the sequence, then SiLU: ``x [B, T,
    H, w]``, ``kernel [taps, H, w]``; position ``t`` sees ``t - taps + 1 ..
    t``, zeros before the row's start."""
    taps, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, i:i + t] * kernel[i]
                           for i in range(taps)))


def _unit(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                         + UNIT_EPS)


def recurrence(q, k, v, g, beta):
    """The rule token by token. ``q, k, g [B, T, H, dk]``, ``v [B, T, H,
    dv]``, ``beta [B, T, H]`` -> ``o [B, T, H, dv]``."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]

    def token(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[..., None]                 # Diag(exp g) S
        old = jnp.einsum('bhkv,bhk->bhv', s, k_t, precision=HIGHEST)
        s = s + jnp.einsum('bhk,bhv->bhkv', k_t, b_t[..., None] * (v_t - old),
                           precision=HIGHEST)
        return s, jnp.einsum('bhkv,bhk->bhv', s, q_t, precision=HIGHEST)

    segment = SCAN_SEGMENT if t % SCAN_SEGMENT == 0 else t

    @jax.checkpoint
    def tokens(s, xs):
        return lax.scan(token, s, xs)

    def split(a):
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((t // segment, segment) + a.shape[1:])

    xs = tuple(split(a) for a in (q, k, v, g, beta))
    _, o = lax.scan(tokens, jnp.zeros((b, h, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(o.reshape((t,) + o.shape[2:]), 0, 1)


def decay(p, x, quant=None):
    """``g [B, T, H, dk]``, the log of a KDA layer's decay (unbounded)."""
    f = _mm('btr,rhk->bthk', _mm('btd,dr->btr', x, p['f_a_proj']['kernel'],
                                 quant), p['f_b_proj']['kernel'], quant)
    return -jnp.exp(p['A_log'])[:, None] * jax.nn.softplus(f + p['dt_bias'])


def _kda(p, x, cfg, quant):
    eps, hd = cfg['rms_norm_eps'], cfg['linear_attn_config']['head_dim']

    def heads(name):
        return _mm('btd,dhk->bthk', x, p[name + '_proj']['kernel'], quant)

    def conv(name):
        return _conv_silu(heads(name), p['conv_' + name])

    q = _unit(conv('q')) * hd ** -0.5
    k = _unit(conv('k'))
    v = conv('v')
    g = decay(p, x, quant)
    beta = 2.0 * jax.nn.sigmoid(
        _mm('btd,dh->bth', x, p['b_proj']['kernel'], quant))
    gate = jax.nn.sigmoid(_mm(
        'btr,rhk->bthk', _mm('btd,dr->btr', x, p['g_a_proj']['kernel'],
                             quant), p['g_b_proj']['kernel'], quant))
    o = _rms(recurrence(q, k, v, g, beta), p['o_norm']['scale'], eps) * gate
    return _mm('bthk,hkd->btd', o, p['o_proj']['kernel'], quant)


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
    hd = cfg['head_dim']
    group = cfg['num_attention_heads'] // cfg['num_key_value_heads']

    def proj(name):
        return _mm('btd,dhk->bthk', x, p[name + '_proj']['kernel'], quant)

    q = proj('q')
    k, v = (jnp.repeat(proj(name), group, axis=2) for name in ('k', 'v'))
    rows = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    blocks = jnp.moveaxis(q.reshape((b, t // rows, rows) + q.shape[2:]), 1, 0)
    out = lax.map(lambda xs: jax.checkpoint(functools.partial(
        _attend, scale=hd ** -0.5, quant=quant))(xs[0], k, v, xs[1]),
        (blocks, rows * jnp.arange(t // rows)))
    out = jnp.moveaxis(out, 0, 1).reshape(q.shape)
    out = out * jax.nn.sigmoid(proj('gate'))
    return _mm('bthk,hkd->btd', out, p['o_proj']['kernel'], quant)


def _swiglu(x, gate, up, down, quant):
    hidden = jax.nn.silu(_mm('btd,df->btf', x, gate, quant)) \
        * _mm('btd,df->btf', x, up, quant)
    return _mm('btf,fd->btd', hidden, down, quant)


def route(p, x, cfg):
    """``(experts [B, T, k], weights [B, T, k])``: the published experts each
    token goes to and what each one's output is weighted by."""
    scores = jax.nn.sigmoid(jnp.einsum('btd,de->bte', x, p['router']['kernel'],
                                       precision=HIGHEST))
    picked, experts = lax.top_k(scores, cfg['num_experts_per_tok'])
    if cfg['norm_topk_prob']:                           # the bias: zeros
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return experts, picked * cfg['routed_scaling_factor']


def _experts(p, x, cfg, quant):
    fe = cfg['moe_intermediate_size']
    sh = p['shared']
    y = _swiglu(x, sh['gate']['kernel'], sh['up']['kernel'],
                sh['down']['kernel'], quant)
    experts, weights = route(p, x, cfg)

    @jax.checkpoint
    def one(y, held):
        """One held expert after another (a scan: the backward pass holds
        one expert's activations at a time)."""
        expert, both, down = held
        mine = jnp.sum(jnp.where(experts == expert, weights, 0.0), axis=-1)
        return y + mine[..., None] * _swiglu(x, both[:, :fe], both[:, fe:],
                                             down, quant), None

    y, _ = lax.scan(one, y, (jnp.asarray(cfg['assumed']['experts_held']),
                             p['experts_gate_up'], p['experts_down']))
    return y


def _block(p, x, mixer, ffn, cfg, quant):
    """One layer; each sub-layer, and each expert, formed again in the
    backward pass (the same numbers, the scratch of one sub-layer at a
    time)."""
    eps = cfg['rms_norm_eps']
    inner = _rms(x, p['mixer_norm']['scale'], eps)
    if mixer == 'kda':
        x = x + jax.checkpoint(functools.partial(_kda, cfg=cfg, quant=quant))(
            p['mixer'], inner)
    else:
        x = x + jax.checkpoint(functools.partial(
            _attention, cfg=cfg, quant=quant))(p['attn'], inner)
    inner = _rms(x, p['ffn_norm']['scale'], eps)
    return x + jax.checkpoint(functools.partial(
        _experts, cfg=cfg, quant=quant))(p['moe'], inner)


def _hidden(params, tokens, cfg, quant):
    """``tokens`` int32 [B, T] -> the final norm's output [B, T, d]."""
    x = params['embed']['embedding'][tokens]
    for i, (mixer, ffn) in enumerate(layer_kinds(cfg)):
        x = jax.checkpoint(functools.partial(
            _block, mixer=mixer, ffn=ffn, cfg=cfg, quant=quant))(
                params['block_{}'.format(i)], x)
    return _rms(x, params['final_norm']['scale'], cfg['rms_norm_eps'])


def logits(params, tokens, cfg, quant=None):
    """``tokens`` int32 [B, T] -> float32 logits [B, T, rows held]."""
    return _mm('btd,dv->btv', _hidden(params, tokens, cfg, quant),
               params['head']['kernel'], quant)


def _token_losses(x, targets, head, quant):
    """Cross-entropy of each position of ``x [B, t, d]`` against
    ``targets [B, t]``."""
    z = _mm('btd,dv->btv', x, head, quant)
    logp = z - jax.scipy.special.logsumexp(z, axis=-1, keepdims=True)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def _loss(params, tokens, cfg, quant, positions):
    """Mean next-token cross-entropy over the first ``positions`` positions
    of rows of ``T + 1`` tokens; the logits formed ``LOSS_BLOCK`` positions
    at a time (the same numbers, a 16th of the memory)."""
    x = _hidden(params, tokens[:, :-1], cfg, quant)
    b, t, d = x.shape
    rows = LOSS_BLOCK if t % LOSS_BLOCK == 0 else t

    def blocks(a):
        return jnp.moveaxis(a.reshape((b, t // rows, rows) + a.shape[2:]),
                            1, 0)

    losses = lax.map(lambda xs: jax.checkpoint(functools.partial(
        _token_losses, head=params['head']['kernel'], quant=quant))(*xs),
        (blocks(x), blocks(tokens[:, 1:])))
    losses = jnp.moveaxis(losses, 0, 1).reshape(b, t)
    return jnp.mean(losses[:, :positions])


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
    return -(-cfg['assumed']['sequence_length'] // cfg['assumed']['chunk'])


def _diagonal_pairs(cfg):
    """Pairs ``i >= j`` inside the sub-blocks of one chunk."""
    c, sub = cfg['assumed']['chunk'], cfg['assumed']['sub_block']
    return c // sub * sub * (sub + 1) // 2


def _rule_forward_flops_per_chunk(cfg):
    """The exact chunked rule's forward on one head's chunk of ``C`` tokens,
    2 operations a multiply-add, a product under the causal mask counted by
    the half that is kept: the decayed ``k k^T`` and ``q k^T`` over the
    pairs before each sub-block (``C (C - S) dk`` each), the sub-blocks' own
    pairs one by one (a difference, an exponential, ``k_j`` times it, and
    the two multiply-adds with ``k_i`` and ``q_i``: 7 a pair and channel),
    the unit-lower system solved for ``w`` and ``u`` by substitution (``C C
    (dk + dv)``), ``p v_new`` (``C C dv``), and the three products with the
    state, ``w S``, ``qg S``, ``kd^T v_new`` (``2 C dk dv`` each)."""
    c, hd = cfg['assumed']['chunk'], cfg['linear_attn_config']['head_dim']
    sub = cfg['assumed']['sub_block']
    return 2 * c * (c - sub) * hd + 7 * _diagonal_pairs(cfg) * hd \
        + c * c * 3 * hd + 6 * c * hd * hd


def _rule_work_per_chunk(cfg):
    """``(operations, bytes)`` of the whole exact rule on one head's chunk,
    forward once and in reverse once: what runs under the name
    ``kda_exact``.

    In reverse, besides what the kernel computes again of the forward pass:
    six products with the state or its gradient (``kd G``, ``do S^T``,
    ``v_new G^T``, ``dV S^T``, ``qg^T do``, ``w^T dV``: ``2 C dk dv`` each),
    ``p^T do`` and the masked ``do v_new^T`` (``C C dv`` each), ``T^T dW``,
    ``T^T dU`` and ``dWb W^T + dUb U^T`` under the mask (``C C (dk + dv)``
    each), the four products back through the decayed ``k k^T`` and ``q
    k^T`` over the pairs before each sub-block (``2 C (C - S) dk`` each),
    and the sub-blocks' pairs in reverse (the exponential and ``k_j`` times
    it formed again, 3; ``dk_i`` and ``dq_i``, 4; the gradient of the
    pair's factor, 3; its sum into ``dk_j``, 2; the exponent's gradient, 1,
    into ``dG_i`` and ``dG_j``, 2: 15 a pair and channel).

    Bytes: forward reads ``q, k, v`` (bfloat16), ``g`` (float32) and ``beta``
    and writes ``o``, the state the chunk starts from and its ``T``
    (bfloat16); the reverse pass reads all of those and ``do`` and writes
    ``dq, dk, dv`` (bfloat16), ``dg`` (float32) and ``dbeta``."""
    c, hd = cfg['assumed']['chunk'], cfg['linear_attn_config']['head_dim']
    sub = cfg['assumed']['sub_block']
    reverse = 12 * c * hd * hd + 2 * c * c * hd + 2 * c * c * 2 * hd \
        + 4 * 2 * c * (c - sub) * hd + 15 * _diagonal_pairs(cfg) * hd
    wide = c * hd
    forward_bytes = 3 * wide * 2 + wide * 4 + c * 4 \
        + wide * 2 + hd * hd * 2 + c * c * 2
    reverse_bytes = forward_bytes + 3 * wide * 2 + wide * 4 + c * 4
    return (_rule_forward_flops_per_chunk(cfg) + reverse,
            forward_bytes + reverse_bytes)


def _kda_flops(cfg):
    """One Kimi-delta mixer's forward on one row: ``W_q, W_k, W_v``,
    ``W_o``, the two low-rank pairs, ``W_b``, and the rule."""
    s, t = _sizes(cfg), cfg['assumed']['sequence_length']
    wide = s['lh'] * s['lhd']
    weights = 4 * s['d'] * wide + 2 * (s['d'] * s['rank'] + s['rank'] * wide) \
        + s['d'] * s['lh']
    return t * 2 * weights \
        + s['lh'] * _chunks_per_row(cfg) * _rule_forward_flops_per_chunk(cfg)


def _attention_flops(cfg):
    """One gated grouped-query attention mixer's forward on one row: the
    five projections, and a causal head's two products by the half that is
    kept (``T T 128`` each)."""
    s, t = _sizes(cfg), cfg['assumed']['sequence_length']
    weights = s['d'] * s['hd'] * (3 * s['h'] + 2 * s['kv'])
    return t * 2 * weights + s['h'] * t * t * 2 * s['hd']


def forward_flops_per_row(cfg):
    """A row is one sequence of ``sequence_length`` positions; what is held
    here only; the routed experts at their expected pairs. Matrix products: 2
    operations a multiply-add."""
    s, t = _sizes(cfg), cfg['assumed']['sequence_length']
    d = s['d']
    moe = t * 2 * (3 * d * s['fs'] + d * s['experts']) \
        + expected_pairs_per_row(cfg) * 2 * 3 * d * s['fe']
    total = t * 2 * d * s['v']
    for mixer, _ in layer_kinds(cfg):
        total += _kda_flops(cfg) if mixer == 'kda' else _attention_flops(cfg)
        total += moe
    return total


def train_flops_per_row(cfg):
    """Forward and backward; what recomputation runs again is not counted."""
    return 3 * forward_flops_per_row(cfg)


def kernels(cfg, rows_per_chip, moe_pairs_per_step=None):
    """The kernels' work in one train step on one chip.

    ``kda_exact``: events named ``kda_exact*`` in the device trace, the two
    Pallas calls of a Kimi-delta layer on the rule's exact path
    (``ops.kimi_delta``, ``exact=True``), which hold the whole rule:
    operations and bytes from :func:`_rule_work_per_chunk`, forward once
    and in reverse once; memory-bound at these shapes (0.179 TFLOP and 2.22
    GB a step on one row: 2.71 ms at a v5e's 819 GB/s against 0.91 ms at its
    197 TFLOP/s). The bounded path (``kda*``, for a decay no lower
    than -5) does not apply: this decay has no bound, and at the assumed
    init most of its entries lie under -5. The time they are set against
    holds the recomputed forward pass too, which the count leaves out.

    ``moe``: events named ``moe*``, the grouped products of the experts held,
    counted as the Ling-3.0-flash configuration counts them (pairs routed, or
    ``None``: the expectation, the two products forward, the same again
    where the block is recomputed, and their four gradient products; every
    array once in bfloat16 a product).

    ``flash``: events named ``attn*``: forward two products, backward five,
    ``2 T T 128`` each, halved by the mask; q, k, v, o and their gradients
    once each in bfloat16."""
    s, t = _sizes(cfg), cfg['assumed']['sequence_length']
    kinds = layer_kinds(cfg)
    mixers = [mixer for mixer, _ in kinds]
    chunks = mixers.count('kda') * s['lh'] * rows_per_chip \
        * _chunks_per_row(cfg)
    flops, moved = _rule_work_per_chunk(cfg)
    kda = {'match': '^kda_exact', 'flops': chunks * flops,
           'bytes': chunks * moved}
    d, fe, held = s['d'], s['fe'], s['held']
    layers = [ffn for _, ffn in kinds].count('moe') * rows_per_chip
    pairs = layers * expected_pairs_per_row(cfg) \
        if moe_pairs_per_step is None else moe_pairs_per_step
    passes = 2 if cfg['assumed']['recompute_each_layer'] else 1
    product = pairs * 2 * 3 * d * fe                    # one forward
    rows_moved = pairs * (d + 2 * fe) + pairs * (fe + d)  # in and out, both
    weights = layers * held * 3 * d * fe
    moe = {'match': '^moe',
           'flops': (passes + 2) * product,
           'bytes': (passes + 2) * 2 * (rows_moved + weights)}
    blocks = mixers.count('gqa') * rows_per_chip
    flash = {'match': '^attn',
             'flops': blocks * s['h'] * 7 * s['hd'] * t * t,
             'bytes': blocks * s['h'] * t * 8 * s['hd'] * 2}
    return {'kda_exact': kda, 'moe': moe, 'flash': flash}
