"""Plain reference of the GPT-2 configuration: decoder-only transformer,
next-token loss, gradients and the AdamW update in straightforward
``jax.numpy`` at float32 and ``Precision.HIGHEST``. Dense causal attention:
no kernel, no flax, nothing of ``petastorm_tpu``. It also makes the weights
(from the seed) and counts operations and bytes (from the shapes).

The tree it makes has the layout the program's flax module reads
(``Embed_0``, ``pos_embed``, ``block_<i>/attn/query`` ...): names, not values.
Departures from the published model are listed in the configuration's json.

``quant`` is the control's hook (``lowprec.Rounding``): it rounds both
operands of every matrix product and the gradient that comes back into it.
``None`` is the reference itself.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
LN_EPS = 1e-6           # flax's default, which the program runs (see json)
ROW_BLOCK = 4           # rows a reference call holds at once


def param_shapes(cfg):
    d, h, v = cfg['n_embd'], cfg['n_head'], cfg['vocab_size']
    hd, inner = d // h, cfg['n_inner']
    shapes = {('Embed_0', 'embedding'): (v, d),
              ('pos_embed', 'embedding'): (cfg['n_positions'], d),
              ('LayerNorm_0', 'scale'): (d,), ('LayerNorm_0', 'bias'): (d,),
              ('head', 'kernel'): (d, v), ('head', 'bias'): (v,)}
    for i in range(cfg['n_layer']):
        b = 'block_{}'.format(i)
        for ln in ('LayerNorm_0', 'LayerNorm_1'):
            shapes[(b, ln, 'scale')] = (d,)
            shapes[(b, ln, 'bias')] = (d,)
        for proj in ('query', 'key', 'value'):
            shapes[(b, 'attn', proj, 'kernel')] = (d, h, hd)
            shapes[(b, 'attn', proj, 'bias')] = (h, hd)
        shapes[(b, 'attn', 'out', 'kernel')] = (h, hd, d)
        shapes[(b, 'attn', 'out', 'bias')] = (d,)
        shapes[(b, 'Dense_0', 'kernel')] = (d, inner)
        shapes[(b, 'Dense_0', 'bias')] = (inner,)
        shapes[(b, 'Dense_1', 'kernel')] = (inner, d)
        shapes[(b, 'Dense_1', 'bias')] = (d,)
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
    """All weights in one jitted call on the device, float32."""
    shapes = param_shapes(cfg)
    residual_scale = 1.0 / np.sqrt(2.0 * cfg['n_layer'])

    @jax.jit
    def make(key):
        flat = {}
        for n, (path, shape) in enumerate(sorted(shapes.items())):
            if path[-1] in ('kernel', 'embedding'):
                std = 0.02
                if path[-2] == 'Dense_1' or path[-3:-1] == ('attn', 'out'):
                    std *= residual_scale
                flat[path] = std * jax.random.normal(
                    jax.random.fold_in(key, n), shape, jnp.float32)
            elif path[-1] == 'scale':
                flat[path] = jnp.ones(shape, jnp.float32)
            else:
                flat[path] = jnp.zeros(shape, jnp.float32)
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


def _ln(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + LN_EPS) * p['scale'] + p['bias']


def _attention(p, x, quant):
    q = _mm('btd,dhk->bthk', x, p['query']['kernel'], quant) + p['query']['bias']
    k = _mm('btd,dhk->bthk', x, p['key']['kernel'], quant) + p['key']['bias']
    v = _mm('btd,dhk->bthk', x, p['value']['kernel'], quant) + p['value']['bias']
    t = x.shape[1]
    scores = _mm('bqhk,bshk->bhqs', q, k, quant) / np.sqrt(q.shape[-1])
    mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _mm('bhqs,bshk->bqhk', probs, v, quant)
    return _mm('bqhk,hkd->bqd', out, p['out']['kernel'], quant) + p['out']['bias']


def _block(p, x, quant):
    x = x + _attention(p['attn'], _ln(x, p['LayerNorm_0']), quant)
    y = _mm('btd,df->btf', _ln(x, p['LayerNorm_1']), p['Dense_0']['kernel'],
            quant) + p['Dense_0']['bias']
    y = jax.nn.gelu(y, approximate=True)
    y = _mm('btf,fd->btd', y, p['Dense_1']['kernel'], quant) + p['Dense_1']['bias']
    return x + y


def logits(params, tokens, cfg, quant=None):
    """``tokens`` int32 [B, T] -> float32 logits [B, T, vocab]."""
    t = tokens.shape[1]
    x = params['Embed_0']['embedding'][tokens]
    x = x + params['pos_embed']['embedding'][jnp.arange(t)][None]
    for i in range(cfg['n_layer']):
        x = jax.checkpoint(functools.partial(_block, quant=quant))(
            params['block_{}'.format(i)], x)
    x = _ln(x, params['LayerNorm_0'])
    return _mm('btd,dv->btv', x, params['head']['kernel'], quant) \
        + params['head']['bias']


def _loss_sum(params, tokens, cfg, quant):
    z = logits(params, tokens[:, :-1], cfg, quant)
    logp = z - jax.scipy.special.logsumexp(z, axis=-1, keepdims=True)
    targets = tokens[:, 1:]
    return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def loss(params, inputs, cfg, quant=None):
    """Mean next-token cross-entropy over every position of every row."""
    tokens = inputs['tokens']
    return _loss_sum(params, tokens, cfg, quant) / (
        tokens.shape[0] * (tokens.shape[1] - 1))


def _freeze(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))))


@functools.lru_cache(maxsize=None)
def _compiled(frozen_cfg, quant):
    cfg = dict(frozen_cfg)
    return jax.jit(jax.value_and_grad(
        lambda p, t: _loss_sum(p, t, cfg, quant)))


@jax.jit
def _accumulate(acc, grads):
    return jax.tree_util.tree_map(jnp.add, acc, grads)


def loss_and_grad(params, inputs, cfg, quant=None, rows_used=None):
    """Rows are independent, so the batch goes through in blocks of
    ``ROW_BLOCK`` rows whose sums are added: the same mean, and float32
    scores of four rows fit where sixteen would not. ``rows_used`` (a fault
    for the tests and the calibration): only that many leading rows enter
    the mean."""
    tokens = inputs['tokens']
    if rows_used is not None:
        tokens = tokens[:rows_used]
    fn = _compiled(_freeze(cfg), quant)
    total, acc = 0.0, None
    block = min(ROW_BLOCK, tokens.shape[0])
    for start in range(0, tokens.shape[0], block):
        value, grads = fn(params, tokens[start:start + block])
        total = total + value
        acc = grads if acc is None else _accumulate(acc, grads)
    count = tokens.shape[0] * (tokens.shape[1] - 1)
    return total / count, jax.tree_util.tree_map(lambda g: g / count, acc)


# -- optimizer: AdamW ----------------------------------------------------------

def opt_init(params, cfg):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {'mu': zeros, 'nu': zeros}


def gradient_as_optimizer_gets_it(grads, params, cfg):
    """AdamW's first moment sees the bare gradient: decay is added after the
    moments, so ``mu_1 / (1 - b1)`` is the gradient itself."""
    return grads


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9))
def _adamw(params, mu, nu, grads, step, lr, b1, b2, eps, wd):
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda n, g: b2 * n + (1 - b2) * g * g,
                                nu, grads)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step

    def move(p, m, n):
        return p - lr * ((m / c1) / (jnp.sqrt(n / c2) + eps) + wd * p)

    return jax.tree_util.tree_map(move, params, mu, nu), mu, nu


def opt_apply(params, opt, grads, cfg, step):
    """``step`` counts from 1."""
    a = cfg['assumed']
    params, mu, nu = _adamw(params, opt['mu'], opt['nu'], grads,
                            jnp.float32(step), a['learning_rate'], a['b1'],
                            a['b2'], a['eps'], a['weight_decay'])
    return params, {'mu': mu, 'nu': nu}


# -- operations and bytes, from the shapes ---------------------------------------

def _attention_flops_per_row(cfg, t):
    """QK^T and PV of a causal head touch half the square: 2 products of
    2*T*T*hd operations a head, halved by the mask."""
    hd = cfg['n_embd'] // cfg['n_head']
    return cfg['n_layer'] * cfg['n_head'] * (2 * 2 * t * t * hd) // 2


def forward_flops_per_row(cfg):
    """A row is one sequence of ``sequence_length`` positions. Matrix
    products only: 2 operations a multiply-add."""
    t = cfg['assumed']['sequence_length']
    d, inner, v = cfg['n_embd'], cfg['n_inner'], cfg['vocab_size']
    per_token = cfg['n_layer'] * (2 * 4 * d * d + 2 * 2 * d * inner) + 2 * d * v
    return t * per_token + _attention_flops_per_row(cfg, t)


def train_flops_per_row(cfg):
    return 3 * forward_flops_per_row(cfg)


def kernels(cfg, rows_per_chip):
    """The flash-attention kernel's work in one train step on one chip:
    events named ``attn*`` in the device trace. Forward is 2 products, the
    backward 5 (recompute S, then dV, dP, dQ, dK), each 2*T*T*hd a head and
    halved by the causal mask. Bytes: q, k, v, o and their four gradients,
    once each, in bfloat16 (the least a kernel that keeps scores on-chip
    must move)."""
    t = cfg['assumed']['sequence_length']
    hd = cfg['n_embd'] // cfg['n_head']
    heads = cfg['n_layer'] * cfg['n_head'] * rows_per_chip
    flops = heads * 7 * (2 * t * t * hd) // 2
    nbytes = heads * 8 * t * hd * 2
    return {'flash': {'match': 'attn', 'flops': flops, 'bytes': nbytes}}
