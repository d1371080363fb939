"""Plain reference of the Ling-3.0-flash configuration: Kimi delta attention
(a delta rule whose decay is a vector a head and token) in five layers of
six, latent attention with no query latent in the sixth, group-limited top-8
routing with a shared expert after one dense layer; loss, gradients and the
AdamW update in straightforward ``jax.numpy`` at float32 and
``Precision.HIGHEST``. The rule is a ``lax.scan`` over tokens, as it is
written, in segments of 64 that the backward pass recomputes; attention is
dense and causal by blocks of queries; the experts are a loop over the
experts held with a mask each; no flax, nothing of ``petastorm_tpu``. It also
makes the weights (from the seed) and counts operations and bytes (from the
shapes).

``d`` = ``hidden_size`` 2560, ``x [T, d]``. Layer ``i`` (pre-norm, one
residual stream, RMSNorm with ``rms_norm_eps``, no bias anywhere)::

    x <- x + mixer_i(rmsnorm(x));   x <- x + ffn_i(rmsnorm(x))

**Kimi delta attention** (``(i + 1) % layer_group_size != 0``; Kimi Linear,
arXiv:2510.26692, section 3), per head, ``d_k = d_v = head_dim`` 128, a state
``S`` in ``R^{128 x 128}`` from zero::

    q = unit(silu(conv4(x W_q))) 128^-1/2;  k = unit(silu(conv4(x W_k)));  v = silu(conv4(x W_v))
    g_t = kda_lower_bound sigmoid(exp(A_log) (x_t W_f + dt_bias))   in (-5, 0)^128
    b_t = sigmoid(x_t W_b)
    S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T;   o_t = S_t^T q_t
    y = W_o [sigmoid(x W_g)_h rmsnorm(o_h)]

``conv4`` is a causal depthwise convolution of ``short_conv_kernel_size`` 4
taps with no bias; ``unit`` the L2 norm over the head (``a / sqrt(|a|^2 +
1e-6)``: assumed); the gate is flash-linear-attention's bounded one
(``kda_safe_gate``, ``kda_lower_bound`` -5; ``W_f`` full rank: ``no_kda_lora``;
``A_log`` a scalar a head, ``dt_bias`` a vector: assumed); the output gate is
one scalar a head (``gated_attention_proj_granularity_type`` ``head_wise``)
and the norm one a head with a shared scale of 128 (``group_norm_size`` 1).

**Latent attention** (``(i + 1) % layer_group_size == 0``; DeepSeek-V3's with
``q_lora_rank`` null)::

    [q_c(128) | q_r(64)] = W_q x  a head
    [c_kv(512) | k_r(64)] = W_DKV x;  c_kv = rmsnorm(c_kv)       (use_qk_norm: assumed to mean this norm)
    [k_c(128) | v(128)] = W_UKV c_kv  a head;  k_r is shared by the heads
    rotary (interleaved pairs, theta 6,000,000, no scaling) on q_r and k_r
    causal softmax((q_c k_c + q_r k_r) 192^-1/2) v;  W_O

**Feed-forward**: layer 0 a SwiGLU of ``intermediate_size`` 6144; the others
``shared(x) + sum over e picked and held of w_e expert_e(x)``, every expert a
SwiGLU of 768. ``s = sigmoid(W_r x)`` over all 512 published experts in
float32; the selection is on ``s + b`` (``b`` zeros and constant: assumed),
by groups (DeepSeek-V3's ``noaux_tc``): the experts lie in ``n_group`` 8
groups of 64 in their order, a group's score is the sum of its two best, the
best ``topk_group`` 4 groups are kept, and the best 8 experts inside them are
picked; weights ``s_e / sum_picked s`` times ``routed_scaling_factor`` 2.5.
The kept layers' entries of ``expert_swiglu_limit_list`` and
``share_expert_swiglu_limit_list`` are 0: no clamp.

Final rmsnorm, an untied head, mean cross-entropy against the next token.

The share: ``num_experts`` in ``cfg`` counts what is held here (the router
stays ``published.num_experts`` wide and ``assumed.experts_held`` names the
experts), ``vocab_size`` the vocabulary's rows held; all 32 heads are held;
what absent chips would add is left out, as in the program.

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
QUERY_BLOCK = 1024      # rows of attention scores held at once
SCAN_SEGMENT = 64       # tokens between two kept states of the recurrence
UNIT_EPS = 1e-6


def _sizes(cfg):
    return dict(
        d=cfg['hidden_size'], v=cfg['vocab_size'],
        h=cfg['num_attention_heads'], hd=cfg['head_dim'],
        taps=cfg['short_conv_kernel_size'], kv_rank=cfg['kv_lora_rank'],
        nope=cfg['qk_nope_head_dim'], rope=cfg['qk_rope_head_dim'],
        vd=cfg['v_head_dim'], f=cfg['intermediate_size'],
        fe=cfg['moe_intermediate_size'], held=cfg['num_experts'],
        experts=cfg['published']['num_experts'],
        fs=cfg['num_shared_experts'] * cfg['moe_shared_expert_intermediate_size'])


def layer_kinds(cfg):
    """``(mixer, feed-forward)`` of every layer: ``'latent'`` where ``(i + 1)
    % layer_group_size == 0`` else ``'kda'``; ``'dense'`` for the first
    ``first_k_dense_replace`` layers, ``'moe'`` after."""
    return [('latent' if (i + 1) % cfg['layer_group_size'] == 0 else 'kda',
             'dense' if i < cfg['first_k_dense_replace'] else 'moe')
            for i in range(cfg['num_hidden_layers'])]


def _block_shapes(b, mixer, ffn, s):
    d, h, hd = s['d'], s['h'], s['hd']
    shapes = {(b, 'mixer_norm', 'scale'): (d,), (b, 'ffn_norm', 'scale'): (d,)}
    if mixer == 'kda':
        m = (b, 'mixer')
        for name in ('q', 'k', 'v'):
            shapes[m + (name + '_proj', 'kernel')] = (d, h, hd)
            shapes[m + ('conv_' + name,)] = (s['taps'], h, hd)
        shapes[m + ('f_proj', 'kernel')] = (d, h, hd)
        shapes[m + ('A_log',)] = (h,)
        shapes[m + ('dt_bias',)] = (h, hd)
        shapes[m + ('b_proj', 'kernel')] = (d, h)
        shapes[m + ('g_proj', 'kernel')] = (d, h)
        shapes[m + ('o_norm', 'scale')] = (hd,)
        shapes[m + ('o_proj', 'kernel')] = (h, hd, d)
    else:
        a = (b, 'attn')
        shapes[a + ('q_proj', 'kernel')] = (d, h, s['nope'] + s['rope'])
        shapes[a + ('kv_down', 'kernel')] = (d, s['kv_rank'] + s['rope'])
        shapes[a + ('kv_norm', 'scale')] = (s['kv_rank'],)
        shapes[a + ('kv_up', 'kernel')] = (s['kv_rank'], h, s['nope'] + s['vd'])
        shapes[a + ('out', 'kernel')] = (h, s['vd'], d)
    if ffn == 'dense':
        for name, shape in (('gate', (d, s['f'])), ('up', (d, s['f'])),
                            ('down', (s['f'], d))):
            shapes[(b, 'mlp', name, 'kernel')] = shape
    else:
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
    taps = cfg['short_conv_kernel_size']

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
                flat[path] = jax.random.uniform(k, shape, jnp.float32,
                                                -1.0, 1.0)
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


def _kda(p, x, cfg, quant):
    eps, hd = cfg['rms_norm_eps'], cfg['head_dim']

    def heads(name):
        return _mm('btd,dhk->bthk', x, p[name + '_proj']['kernel'], quant)

    def conv(name):
        return _conv_silu(heads(name), p['conv_' + name])

    q = _unit(conv('q')) * hd ** -0.5
    k = _unit(conv('k'))
    v = conv('v')
    g = cfg['kda_lower_bound'] * jax.nn.sigmoid(
        jnp.exp(p['A_log'])[:, None] * (heads('f') + p['dt_bias']))
    beta = jax.nn.sigmoid(_mm('btd,dh->bth', x, p['b_proj']['kernel'], quant))
    gate = jax.nn.sigmoid(_mm('btd,dh->bth', x, p['g_proj']['kernel'], quant))
    o = _rms(recurrence(q, k, v, g, beta), p['o_norm']['scale'], eps) \
        * gate[..., None]
    return _mm('bthk,hkd->btd', o, p['o_proj']['kernel'], quant)


def inv_freq(cfg):
    """``rope / 2`` plain rotary frequencies, ``theta ** (-2 i / rope)``."""
    dim = cfg['qk_rope_head_dim']
    return 1.0 / cfg['rope_theta'] ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)


def _rotate(x, cfg):
    """Interleaved pairs ``(x[2i], x[2i+1])`` of the last axis turned by
    ``position * frequency_i``; ``x [B, T, ..., rope]``."""
    t = x.shape[1]
    angles = jnp.asarray(np.arange(t)[:, None] * inv_freq(cfg)[None, :],
                         jnp.float32)
    angles = angles.reshape((1, t) + (1,) * (x.ndim - 3) + (-1,))
    pairs = x.reshape(x.shape[:-1] + (-1, 2))
    x0, x1 = pairs[..., 0], pairs[..., 1]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                     axis=-1).reshape(x.shape)


def _attend(q, k, v, first, scale, quant):
    """Queries ``first .. first + rows`` against every key up to each."""
    scores = _mm('bqhk,bshk->bhqs', q, k, quant) * scale
    mask = (first + jnp.arange(q.shape[1]))[:, None] >= jnp.arange(
        k.shape[1])[None, :]
    probs = jax.nn.softmax(jnp.where(mask[None, None], scores, -jnp.inf),
                           axis=-1)
    return _mm('bhqs,bshk->bqhk', probs, v, quant)


def _attention(p, x, cfg, quant):
    eps, nope, rank = (cfg['rms_norm_eps'], cfg['qk_nope_head_dim'],
                       cfg['kv_lora_rank'])
    b, t, _ = x.shape
    q = _mm('btd,dhk->bthk', x, p['q_proj']['kernel'], quant)
    kv = _mm('btd,dr->btr', x, p['kv_down']['kernel'], quant)
    c_kv = _rms(kv[..., :rank], p['kv_norm']['scale'], eps)
    k_r = _rotate(kv[..., rank:], cfg)
    up = _mm('btr,rhk->bthk', c_kv, p['kv_up']['kernel'], quant)
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], cfg)], axis=-1)
    k = jnp.concatenate([up[..., :nope], jnp.broadcast_to(
        k_r[:, :, None, :], up.shape[:3] + k_r.shape[-1:])], axis=-1)
    v = up[..., nope:]
    rows = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    blocks = jnp.moveaxis(q.reshape((b, t // rows, rows) + q.shape[2:]), 1, 0)
    out = lax.map(lambda xs: jax.checkpoint(functools.partial(
        _attend, scale=cfg['qk_head_dim'] ** -0.5, quant=quant))(
            xs[0], k, v, xs[1]), (blocks, rows * jnp.arange(t // rows)))
    out = jnp.moveaxis(out, 0, 1).reshape(v.shape)
    return _mm('bthk,hkd->btd', out, p['out']['kernel'], quant)


def _swiglu(x, gate, up, down, quant):
    hidden = jax.nn.silu(_mm('btd,df->btf', x, gate, quant)) \
        * _mm('btd,df->btf', x, up, quant)
    return _mm('btf,fd->btd', hidden, down, quant)


def select(scores, cfg):
    """The published experts each token goes to, ``[..., k]``: the best
    ``num_experts_per_tok`` among the experts of the best ``topk_group`` of
    ``n_group`` groups, a group scored by the sum of its two best."""
    groups = cfg['n_group']
    grouped = scores.reshape(scores.shape[:-1] + (groups, -1))
    group_score = jnp.sum(lax.top_k(grouped, 2)[0], axis=-1)
    _, best = lax.top_k(group_score, cfg['topk_group'])
    kept = jnp.any(best[..., None] == jnp.arange(groups), axis=-2)
    allowed = jnp.where(kept[..., None], grouped, -jnp.inf)
    return lax.top_k(allowed.reshape(scores.shape),
                     cfg['num_experts_per_tok'])[1]


def route(p, x, cfg):
    """``(experts [B, T, k], weights [B, T, k])``: the published experts each
    token goes to and what each one's output is weighted by."""
    scores = jax.nn.sigmoid(jnp.einsum('btd,de->bte', x, p['router']['kernel'],
                                       precision=HIGHEST))
    experts = select(scores, cfg)                       # the bias: zeros
    picked = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg['norm_topk_prob']:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return experts, picked * cfg['routed_scaling_factor']


def _experts(p, x, cfg, quant):
    fe = cfg['moe_intermediate_size']
    sh = p['shared']
    y = _swiglu(x, sh['gate']['kernel'], sh['up']['kernel'],
                sh['down']['kernel'], quant)
    experts, weights = route(p, x, cfg)
    for slot, expert in enumerate(cfg['assumed']['experts_held']):
        mine = jnp.sum(jnp.where(experts == expert, weights, 0.0), axis=-1)
        both = p['experts_gate_up'][slot]
        y = y + mine[..., None] * _swiglu(
            x, both[:, :fe], both[:, fe:], p['experts_down'][slot], quant)
    return y


def _block(p, x, mixer, ffn, cfg, quant):
    eps = cfg['rms_norm_eps']
    inner = _rms(x, p['mixer_norm']['scale'], eps)
    if mixer == 'kda':
        x = x + _kda(p['mixer'], inner, cfg, quant)
    else:
        x = x + _attention(p['attn'], inner, cfg, quant)
    inner = _rms(x, p['ffn_norm']['scale'], eps)
    if ffn == 'dense':
        m = p['mlp']
        return x + _swiglu(inner, m['gate']['kernel'], m['up']['kernel'],
                           m['down']['kernel'], quant)
    return x + _experts(p['moe'], inner, cfg, quant)


def logits(params, tokens, cfg, quant=None):
    """``tokens`` int32 [B, T] -> float32 logits [B, T, rows held]."""
    x = params['embed']['embedding'][tokens]
    for i, (mixer, ffn) in enumerate(layer_kinds(cfg)):
        x = jax.checkpoint(functools.partial(
            _block, mixer=mixer, ffn=ffn, cfg=cfg, quant=quant))(
                params['block_{}'.format(i)], x)
    x = _rms(x, params['final_norm']['scale'], cfg['rms_norm_eps'])
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
        * cfg['num_experts'] // cfg['published']['num_experts']


def _chunks_per_row(cfg):
    return -(-cfg['assumed']['sequence_length'] // cfg['assumed']['chunk'])


def _rule_forward_flops_per_chunk(cfg):
    """The chunked rule's forward on one head's chunk of ``C`` tokens, 2
    operations a multiply-add, a product under the causal mask counted by the
    half that is kept: the decayed ``k k^T`` and ``q k^T`` (``C C dk`` each,
    whatever the sub-blocks run beyond that), the unit-lower system solved
    for ``w`` and ``u`` by substitution (``C C (dk + dv)``), ``p v_new`` (``C
    C dv``), and the three products with the state, ``w S``, ``qg S``,
    ``kd^T v_new`` (``2 C dk dv`` each)."""
    c, hd = cfg['assumed']['chunk'], cfg['head_dim']
    return c * c * 5 * hd + 6 * c * hd * hd


def _rule_work_per_chunk(cfg):
    """``(operations, bytes)`` of the whole rule on one head's chunk, forward
    once and in reverse once: what runs under the name ``kda``.

    In reverse, besides what the kernel computes again of the forward pass:
    six products with the state or its gradient (``kd G``, ``do S^T``,
    ``v_new G^T``, ``dV S^T``, ``qg^T do``, ``w^T dV``: ``2 C dk dv`` each),
    ``p^T do`` and the masked ``do v_new^T`` (``C C dv`` each), ``T^T dW``,
    ``T^T dU`` and ``dWb W^T + dUb U^T`` under the mask (``C C (dk + dv)``
    each), and the four products back through the decayed ``k k^T`` and ``q
    k^T`` (``C C dk`` each).

    Bytes: forward reads ``q, k, v`` (bfloat16), ``g`` (float32) and ``beta``
    and writes ``o``, the state the chunk starts from and its ``T``
    (bfloat16); the reverse pass reads all of those and ``do`` and writes
    ``dq, dk, dv`` (bfloat16), ``dg`` (float32) and ``dbeta``."""
    c, hd = cfg['assumed']['chunk'], cfg['head_dim']
    reverse = 12 * c * hd * hd + 2 * c * c * hd + 2 * c * c * 2 * hd \
        + 4 * c * c * hd
    wide = c * hd
    forward_bytes = 3 * wide * 2 + wide * 4 + c * 4 \
        + wide * 2 + hd * hd * 2 + c * c * 2
    reverse_bytes = forward_bytes + 3 * wide * 2 + wide * 4 + c * 4
    return (_rule_forward_flops_per_chunk(cfg) + reverse,
            forward_bytes + reverse_bytes)


def _kda_flops(cfg):
    """One Kimi-delta mixer's forward on one row: ``W_q, W_k, W_v, W_f``,
    ``W_o``, the two head-wise projections, and the rule."""
    s, t = _sizes(cfg), cfg['assumed']['sequence_length']
    return t * 2 * s['d'] * s['h'] * (5 * s['hd'] + 2) \
        + s['h'] * _chunks_per_row(cfg) * _rule_forward_flops_per_chunk(cfg)


def _attention_flops(cfg):
    """One latent-attention mixer's forward on one row: the four
    projections, and a causal head's two products by the half that is kept
    (``T T 192`` for the scores, ``T T 128`` for the values)."""
    s, t = _sizes(cfg), cfg['assumed']['sequence_length']
    qk = s['nope'] + s['rope']
    weights = s['d'] * s['h'] * qk + s['d'] * (s['kv_rank'] + s['rope']) \
        + s['kv_rank'] * s['h'] * (s['nope'] + s['vd']) + s['h'] * s['vd'] * s['d']
    return t * 2 * weights + s['h'] * t * t * (qk + s['vd'])


def forward_flops_per_row(cfg):
    """A row is one sequence of ``sequence_length`` positions; what is held
    here only; the routed experts at their expected pairs. Matrix products: 2
    operations a multiply-add."""
    s, t = _sizes(cfg), cfg['assumed']['sequence_length']
    d = s['d']
    dense = t * 2 * 3 * d * s['f']
    moe = t * 2 * (3 * d * s['fs'] + d * s['experts']) \
        + expected_pairs_per_row(cfg) * 2 * 3 * d * s['fe']
    total = t * 2 * d * s['v']
    for mixer, ffn in layer_kinds(cfg):
        total += _kda_flops(cfg) if mixer == 'kda' else _attention_flops(cfg)
        total += dense if ffn == 'dense' else moe
    return total


def train_flops_per_row(cfg):
    """Forward and backward; what recomputation runs again is not counted."""
    return 3 * forward_flops_per_row(cfg)


def kernels(cfg, rows_per_chip, moe_pairs_per_step=None):
    """The kernels' work in one train step on one chip.

    ``kda``: events named ``kda*`` in the device trace, the two Pallas calls
    of a Kimi-delta layer (``ops.kimi_delta``), which hold the whole rule:
    operations and bytes from :func:`_rule_work_per_chunk`, forward once and
    in reverse once. The time they are set against holds the recomputed
    forward pass too, which the count leaves out.

    ``moe``: events named ``moe*``, the grouped products of the experts held,
    counted as the Xing4.0 configuration counts them: for the pairs routed to
    them in a step, summed over the expert layers (``moe_pairs_per_step``;
    ``None``: the expectation, 128 an expert a layer a row), the two products
    forward, the same again where the block is recomputed, and their four
    gradient products; every array once in bfloat16 a product.

    ``flash``: events named ``attn*``: forward two products, backward five,
    ``2 T T w`` each, halved by the mask: four of the key width 192, three of
    the value width 128; q, k and their gradients 192 wide, v, o and theirs
    128, once each in bfloat16."""
    s, t = _sizes(cfg), cfg['assumed']['sequence_length']
    kinds = layer_kinds(cfg)
    mixers = [mixer for mixer, _ in kinds]
    chunks = mixers.count('kda') * s['h'] * rows_per_chip * _chunks_per_row(cfg)
    flops, moved = _rule_work_per_chunk(cfg)
    kda = {'match': '^kda', 'flops': chunks * flops, 'bytes': chunks * moved}
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
    blocks = mixers.count('latent') * rows_per_chip
    qk, vd = s['nope'] + s['rope'], s['vd']
    flash = {'match': '^attn',
             'flops': blocks * s['h'] * (4 * qk + 3 * vd) * t * t,
             'bytes': blocks * s['h'] * t * 4 * (qk + vd) * 2}
    return {'kda': kda, 'moe': moe, 'flash': flash}
