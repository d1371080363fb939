"""Plain reference of the Olmo-Hybrid configuration: gated delta-rule layers
with a full-attention layer among every four, next-token loss, gradients and
the AdamW update in straightforward ``jax.numpy`` at float32 and
``Precision.HIGHEST``. The recurrence is a ``lax.scan`` over tokens, as it is
written down (no chunks, no kernel); attention is dense and causal; no flax,
nothing of ``petastorm_tpu``. It also makes the weights (from the seed) and
counts operations and bytes (from the shapes).

A layer, ``x`` the residual stream (configuration json, ``equations``)::

    x = x + rmsnorm(mixer(x));  x = x + rmsnorm(W_down(silu(W_gate x) * W_up x))

    linear_attention   q, k, v = silu(conv4(W x)) (causal, depthwise);
                       q, k unit length a head, q times dk^-1/2;
                       b = 2 sigmoid(W_b x);  a = exp(-exp(A_log) softplus(W_a x + dt_bias))
                       S_t = a_t S_{t-1} (I - b_t k_t k_t^T) + b_t v_t k_t^T;  o_t = S_t q_t
                       out = W_o (rmsnorm_head(o) * silu(W_g x))
    full_attention     q, k, v = W x; q, k = rmsnorm(q), rmsnorm(k) over the
                       columns held; causal softmax(q k^T / sqrt(128)) v; W_out

The share: every count of heads in ``cfg`` is of the heads held here, and
``vocab_size`` of the vocabulary's rows held; what absent chips would add is
left out, as in the program.

The tree it makes has the layout the program's flax module reads
(``embed``, ``block_<i>/mixer/q_proj`` ...): names, not values.

``quant`` is the control's hook (``lowprec.Rounding``): it rounds both
operands of every projection's and attention's matrix product and the
gradient that comes back into it; the recurrence's state stays float32, as
an fp8 recipe keeps it. ``None`` is the reference itself.

Memory, at one row of 8,192 tokens beside 3.07 GB of weights: each layer is
recomputed in the backward pass, attention goes by blocks of 1,024 queries,
the scan keeps its state once a segment of 64 tokens (the same recurrence,
token by token; the segments only say what the backward pass keeps), and
AdamW's moments live on the host between steps, a leaf at a time on the
chip: the comparison holds the weights twice and a gradient beside them.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 1024      # rows of attention scores held at once
SCAN_SEGMENT = 64       # tokens between two kept states of the recurrence


def param_shapes(cfg):
    d, f, v = cfg['hidden_size'], cfg['intermediate_size'], cfg['vocab_size']
    h_full, hd = cfg['num_attention_heads'], cfg['head_dim']
    h_lin = cfg['linear_num_value_heads']
    dk, dv = cfg['linear_key_head_dim'], cfg['linear_value_head_dim']
    taps = cfg['linear_conv_kernel_dim']
    shapes = {('embed', 'embedding'): (v, d), ('final_norm', 'scale'): (d,),
              ('head', 'kernel'): (d, v)}
    for i, kind in enumerate(cfg['layer_types']):
        b = 'block_{}'.format(i)
        if kind == 'linear_attention':
            m = 'mixer'
            for name, width in (('q', dk), ('k', dk), ('v', dv)):
                shapes[(b, m, name + '_proj', 'kernel')] = (d, h_lin, width)
                shapes[(b, m, 'conv_' + name)] = (taps, h_lin, width)
            shapes[(b, m, 'g_proj', 'kernel')] = (d, h_lin, dv)
            shapes[(b, m, 'a_proj', 'kernel')] = (d, h_lin)
            shapes[(b, m, 'b_proj', 'kernel')] = (d, h_lin)
            shapes[(b, m, 'A_log')] = (h_lin,)
            shapes[(b, m, 'dt_bias')] = (h_lin,)
            shapes[(b, m, 'o_norm', 'scale')] = (dv,)
            shapes[(b, m, 'o_proj', 'kernel')] = (h_lin, dv, d)
        else:
            m = 'attn'
            for name in ('query', 'key', 'value'):
                shapes[(b, m, name, 'kernel')] = (d, h_full, hd)
            shapes[(b, m, 'q_norm', 'scale')] = (h_full * hd,)
            shapes[(b, m, 'k_norm', 'scale')] = (h_full * hd,)
            shapes[(b, m, 'out', 'kernel')] = (h_full, hd, d)
        shapes[(b, 'mixer_norm', 'scale')] = (d,)
        shapes[(b, 'mlp_norm', 'scale')] = (d,)
        shapes[(b, 'mlp', 'gate', 'kernel')] = (d, f)
        shapes[(b, 'mlp', 'up', 'kernel')] = (d, f)
        shapes[(b, 'mlp', 'down', 'kernel')] = (f, d)
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
    taps = cfg['linear_conv_kernel_dim']

    @jax.jit
    def make(key):
        flat = {}
        for n, (path, shape) in enumerate(sorted(shapes.items())):
            k = jax.random.fold_in(key, n)
            if path[-1] == 'embedding':
                flat[path] = jax.random.normal(k, shape, jnp.float32)
            elif path[-1] == 'kernel':
                flat[path] = 0.02 * jax.random.normal(k, shape, jnp.float32)
            elif path[-1].startswith('conv_'):
                bound = 1.0 / np.sqrt(taps)
                flat[path] = jax.random.uniform(k, shape, jnp.float32,
                                                -bound, bound)
            elif path[-1] == 'A_log':
                flat[path] = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                                        1.0, 16.0))
            elif path[-1] == 'dt_bias':
                dt = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
                flat[path] = dt + jnp.log(-jnp.expm1(-dt))   # softplus^-1(dt)
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
    """``x [B, T, H, W]``, ``kernel [K, H, W]``: position t sees t-K+1 .. t."""
    taps, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, i:i + t] * kernel[i]
                           for i in range(taps)))


def _unit(x, eps):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def _recurrence(q, k, v, decay, beta):
    """The gated delta rule token by token. ``q, k [B, T, H, dk]``,
    ``v [B, T, H, dv]``, ``decay, beta [B, T, H]`` -> ``o [B, T, H, dv]``."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]

    def token(s, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        s = s * a_t[..., None, None]
        old = jnp.einsum('bhvk,bhk->bhv', s, k_t, precision=HIGHEST)
        s = s + jnp.einsum('bhv,bhk->bhvk', b_t[..., None] * (v_t - old), k_t,
                           precision=HIGHEST)
        return s, jnp.einsum('bhvk,bhk->bhv', s, q_t, precision=HIGHEST)

    segment = SCAN_SEGMENT if t % SCAN_SEGMENT == 0 else t

    @jax.checkpoint
    def tokens(s, xs):
        return lax.scan(token, s, xs)

    def split(a):
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((t // segment, segment) + a.shape[1:])

    xs = tuple(split(a) for a in (q, k, v, decay, beta))
    _, o = lax.scan(tokens, jnp.zeros((b, h, dv, dk), jnp.float32), xs)
    return jnp.moveaxis(o.reshape((t,) + o.shape[2:]), 0, 1)


def _linear_attention(p, x, cfg, quant):
    eps, dk = cfg['rms_norm_eps'], cfg['linear_key_head_dim']

    def conv(name):
        return _conv_silu(_mm('btd,dhk->bthk', x, p[name + '_proj']['kernel'],
                              quant), p['conv_' + name])

    q = _unit(conv('q'), eps) * dk ** -0.5
    k = _unit(conv('k'), eps)
    v = conv('v')
    a = _mm('btd,dh->bth', x, p['a_proj']['kernel'], quant)
    b = _mm('btd,dh->bth', x, p['b_proj']['kernel'], quant)
    decay = jnp.exp(-jnp.exp(p['A_log']) * jax.nn.softplus(a + p['dt_bias']))
    beta = 2.0 * jax.nn.sigmoid(b)
    o = _recurrence(q, k, v, decay, beta)
    o = _rms(o, p['o_norm']['scale'], eps) * jax.nn.silu(
        _mm('btd,dhk->bthk', x, p['g_proj']['kernel'], quant))
    return _mm('bthk,hkd->btd', o, p['o_proj']['kernel'], quant)


def _attend(q, k, v, first, quant):
    """Queries ``first .. first + rows`` against every key up to each."""
    scores = _mm('bqhk,bshk->bhqs', q, k, quant) / np.sqrt(q.shape[-1])
    mask = (first + jnp.arange(q.shape[1]))[:, None] >= jnp.arange(
        k.shape[1])[None, :]
    probs = jax.nn.softmax(jnp.where(mask[None, None], scores, -jnp.inf),
                           axis=-1)
    return _mm('bhqs,bshk->bqhk', probs, v, quant)


def _full_attention(p, x, cfg, quant):
    eps = cfg['rms_norm_eps']
    b, t, _ = x.shape

    def proj(name):
        return _mm('btd,dhk->bthk', x, p[name]['kernel'], quant)

    def normed(a, name):
        return _rms(a.reshape(b, t, -1), p[name]['scale'], eps).reshape(a.shape)

    q, k, v = normed(proj('query'), 'q_norm'), normed(proj('key'), 'k_norm'), \
        proj('value')
    rows = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    blocks = jnp.moveaxis(q.reshape((b, t // rows, rows) + q.shape[2:]), 1, 0)
    out = lax.map(lambda xs: jax.checkpoint(
        functools.partial(_attend, quant=quant))(xs[0], k, v, xs[1]),
        (blocks, rows * jnp.arange(t // rows)))
    out = jnp.moveaxis(out, 0, 1).reshape(q.shape)
    return _mm('bthk,hkd->btd', out, p['out']['kernel'], quant)


def _block(p, x, kind, cfg, quant):
    eps = cfg['rms_norm_eps']
    if kind == 'linear_attention':
        y = _linear_attention(p['mixer'], x, cfg, quant)
    else:
        y = _full_attention(p['attn'], x, cfg, quant)
    x = x + _rms(y, p['mixer_norm']['scale'], eps)
    gate = _mm('btd,df->btf', x, p['mlp']['gate']['kernel'], quant)
    up = _mm('btd,df->btf', x, p['mlp']['up']['kernel'], quant)
    y = _mm('btf,fd->btd', jax.nn.silu(gate) * up, p['mlp']['down']['kernel'],
            quant)
    return x + _rms(y, p['mlp_norm']['scale'], eps)


def logits(params, tokens, cfg, quant=None):
    """``tokens`` int32 [B, T] -> float32 logits [B, T, vocabulary rows held]."""
    x = params['embed']['embedding'][tokens]
    for i, kind in enumerate(cfg['layer_types']):
        x = jax.checkpoint(functools.partial(
            _block, kind=kind, cfg=cfg, quant=quant))(
                params['block_{}'.format(i)], x)
    x = _rms(x, params['final_norm']['scale'], cfg['rms_norm_eps'])
    return _mm('btd,dv->btv', x, params['head']['kernel'], quant)


def _loss_sum(params, tokens, cfg, quant, positions):
    z = logits(params, tokens[:, :-1], cfg, quant)
    logp = z - jax.scipy.special.logsumexp(z, axis=-1, keepdims=True)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.sum(picked[:, :positions])


def loss(params, inputs, cfg, quant=None):
    """Mean next-token cross-entropy over every position of every row."""
    tokens = inputs['tokens']
    t = tokens.shape[1] - 1
    return _loss_sum(params, tokens, cfg, quant, t) / (tokens.shape[0] * t)


def _freeze(cfg):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()
                        if isinstance(v, (int, float, str))
                        or k == 'layer_types'))


@functools.lru_cache(maxsize=None)
def _compiled(frozen_cfg, quant, positions):
    cfg = dict(frozen_cfg)
    return jax.jit(jax.value_and_grad(
        lambda p, t: _loss_sum(p, t, cfg, quant, positions)))


@jax.jit
def _accumulate(acc, grads):
    return jax.tree_util.tree_map(jnp.add, acc, grads)


def loss_and_grad(params, inputs, cfg, quant=None, rows_used=None):
    """One row at a time, sums added: the same mean. ``rows_used`` (a fault
    for the tests and the calibration): only that many leading rows enter
    the mean; where a step is one row, half of a step is the first half of
    the row's positions, which is what ``rows_used`` 0 takes."""
    tokens = inputs['tokens']
    positions = tokens.shape[1] - 1
    if rows_used is not None:
        if rows_used < 1:
            positions //= 2
        else:
            tokens = tokens[:rows_used]
    fn = _compiled(_freeze(cfg), quant, positions)
    total, acc = 0.0, None
    for start in range(tokens.shape[0]):
        value, grads = fn(params, tokens[start:start + 1])
        total = total + value
        acc = grads if acc is None else _accumulate(acc, grads)
    count = tokens.shape[0] * positions
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

def _rule_forward_flops_per_chunk(cfg):
    """The chunked gated delta rule's forward on one head's chunk of ``C``
    tokens, 2 operations a multiply-add, a product under the causal mask
    counted by the half that is kept: ``k k^T`` and ``q k^T`` (``C C dk``
    each), the unit-lower system solved for ``w`` and ``u`` by substitution
    (``C C (dk + dv)``), ``p v_new`` (``C C dv``), and the three products
    with the state, ``w h``, ``qg h``, ``kg^T v_new`` (``2 C dk dv`` each)."""
    c = cfg['assumed']['chunk']
    dk, dv = cfg['linear_key_head_dim'], cfg['linear_value_head_dim']
    return c * c * (3 * dk + 2 * dv) + 6 * c * dk * dv


def _rule_chunks_per_row(cfg):
    return -(-cfg['assumed']['sequence_length'] // cfg['assumed']['chunk'])


def _pass_work_per_chunk(cfg):
    """``(operations, bytes)`` of the pass over chunks on one head's chunk,
    forward once and in reverse once: the part of the rule that runs under
    the name ``gdn`` (the transform that feeds it runs as XLA fusions under
    other names and is counted in ``train_flops_per_row`` only).

    Forward, four products: ``w h``, ``qg h``, ``kg^T v_new`` (``2 C dk dv``
    each) and ``p v_new`` under the causal mask (``C C dv``, the half that is
    kept). In reverse, eight: ``kg G``, ``do h^T``, ``v_new G^T``,
    ``dV h^T``, ``qg^T do``, ``w^T dV`` (``2 C dk dv`` each), ``p^T do`` and
    the masked ``do v_new^T`` (``C C dv`` each).

    Bytes, every array once in bfloat16: forward reads ``qg, kg, w``
    (``C dk``), ``p`` (``C C``), ``u`` (``C dv``) and writes ``o``,
    ``v_new`` (``C dv``) and the state the chunk starts from (``dk dv``);
    the reverse pass reads ``do``, ``v_new``, ``qg, kg, w``, ``p``, that
    state, and writes ``dqg, dkg, dw``, ``dp``, ``du``; and three float32
    rows of ``dv`` (the chunk's decay twice, its gradient once)."""
    c = cfg['assumed']['chunk']
    dk, dv = cfg['linear_key_head_dim'], cfg['linear_value_head_dim']
    flops = (3 + 6) * 2 * c * dk * dv + (1 + 2) * c * c * dv
    elements = (3 + 6) * c * dk + (1 + 2) * c * c + (3 + 3) * c * dv \
        + (1 + 1) * dk * dv
    return flops, 2 * elements + 3 * 4 * dv


def forward_flops_per_row(cfg):
    """A row is one sequence of ``sequence_length`` positions; what is held
    here only. Matrix products: 2 operations a multiply-add."""
    t = cfg['assumed']['sequence_length']
    d, f, v = cfg['hidden_size'], cfg['intermediate_size'], cfg['vocab_size']
    h_full, hd = cfg['num_attention_heads'], cfg['head_dim']
    h_lin = cfg['linear_num_value_heads']
    dk, dv = cfg['linear_key_head_dim'], cfg['linear_value_head_dim']
    total = t * 2 * d * v
    for kind in cfg['layer_types']:
        total += t * 2 * 3 * d * f
        if kind == 'linear_attention':
            total += t * 2 * d * h_lin * (2 * dk + 3 * dv + 2)
            total += h_lin * _rule_chunks_per_row(cfg) * \
                _rule_forward_flops_per_chunk(cfg)
        else:
            total += t * 2 * 4 * d * h_full * hd
            # QK^T and PV of a causal head touch half the square
            total += h_full * (2 * 2 * t * t * hd) // 2
    return total


def train_flops_per_row(cfg):
    """Forward and backward; what recomputation runs again is not counted."""
    return 3 * forward_flops_per_row(cfg)


def kernels(cfg, rows_per_chip):
    """The kernels' work in one train step on one chip.

    ``gdn``: events named ``gdn*`` in the device trace, which are the two
    Pallas calls of the pass over chunks; operations and bytes are that
    pass's own (:func:`_pass_work_per_chunk`), forward once and in reverse
    once, a function of the shapes alone. The time they are set against
    holds the recomputed forward pass too, which the count leaves out.

    ``flash``: events named ``attn*``, counted as the GPT-2 configuration
    counts them (7 products of ``2 T T hd`` a head, halved by the mask; q,
    k, v, o and their gradients once each in bfloat16)."""
    kinds = cfg['layer_types']
    chunks = kinds.count('linear_attention') * cfg['linear_num_value_heads'] \
        * rows_per_chip * _rule_chunks_per_row(cfg)
    flops, moved = _pass_work_per_chunk(cfg)
    gdn = {'match': '^gdn', 'flops': chunks * flops, 'bytes': chunks * moved}
    t, hd = cfg['assumed']['sequence_length'], cfg['head_dim']
    heads = kinds.count('full_attention') * cfg['num_attention_heads'] \
        * rows_per_chip
    flash = {'match': '^attn', 'flops': heads * 7 * (2 * t * t * hd) // 2,
             'bytes': heads * 8 * t * hd * 2}
    return {'gdn': gdn, 'flash': flash}
