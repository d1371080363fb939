"""Plain reference of the Xing4.0-29B-A4B configuration: four residual streams
mixed by Sinkhorn-normalised matrices, latent attention with rotary keys,
dropless top-4 routing with a shared expert, a second-next-token module;
both losses, gradients and the AdamW update in straightforward ``jax.numpy``
at float32 and ``Precision.HIGHEST``. Attention is dense and causal by blocks
of queries, the experts are a loop over the experts held with a mask each,
Sinkhorn is the loop it is written as; no flax, nothing of ``petastorm_tpu``.
It also makes the weights (from the seed) and counts operations and bytes
(from the shapes).

``d`` = ``hidden_size``, ``n`` = ``hc_mult`` streams, ``X [T, n, d]``.

**Streams** (manifold-constrained hyper-connections, arXiv:2512.24880, after
arXiv:2409.19606). ``X_0`` is the embedding copied to the ``n`` streams. A
sub-layer ``F`` (attention, or feed-forward / experts) has its own maps, per
token ``t``::

    x~     = rmsnorm(vec(X_t)) in R^{nd}                      (eps hc_eps, scale [nd])
    H_pre  = sigmoid(a_pre  x~ Phi_pre  + b_pre)   in R^n
    H_post = 2 sigmoid(a_post x~ Phi_post + b_post) in R^n
    H_res  = sinkhorn(exp(clip(a_res mat(x~ Phi_res) + b_res, -30, 30))) in R^{n x n}
    X_t   <- H_res X_t + H_post^T F(rmsnorm(H_pre X_t))

``sinkhorn`` is ``hc_sinkhorn_iters`` = 20 times: every row over its sum plus
``hc_eps``, then every column over its sum plus ``hc_eps`` (assumed: rows
first, the eps in the denominators). After the last layer the streams are
summed, then the final rmsnorm and the head (assumed).

**Latent attention** (DeepSeek-V3's)::

    c_q = rmsnorm(W_DQ x);  [q_c(128) | q_r(64)] = W_UQ c_q  a head
    [c_kv(512) | k_r(64)] = W_DKV x;  c_kv = rmsnorm(c_kv)
    [k_c(128) | v(128)] = W_UKV c_kv  a head;  k_r is shared by the heads
    rotary (interleaved pairs, yarn frequencies) on q_r and k_r
    causal softmax((q_c k_c + q_r k_r) 192^-1/2 m^2) v;  m = 0.1 ln 64 + 1;  W_O

**Experts**: ``s = sigmoid(W_r x)`` over all 64 published experts in float32;
the top 4 of ``s + b`` (``b`` zeros and constant: assumed); weights ``s_e /
sum_top4 s`` times ``routed_scaling_factor``; output ``shared(x) + sum over e
in top4 and held of w_e expert_e(x)``, every expert a SwiGLU of width 1024.
The first layer has a SwiGLU of 9216 in the experts' place.

**Second-next token** (DeepSeek-V3's): ``h' = W_eh [rmsnorm(h_i) ;
rmsnorm(Emb(t_{i+1}))]``, ``h`` the summed streams before the final norm; one
more expert block with its own streams; the shared final norm and head;
cross-entropy against ``t_{i+2}``, the row's last position left out of it.
Total loss = next-token mean + ``mtp_loss_weight`` (0.3, assumed) times this
mean.

The share: ``num_attention_heads`` and ``n_routed_experts`` in ``cfg`` count
what is held here (the router stays ``published.n_routed_experts`` wide and
``assumed.experts_held`` names the experts), ``vocab_size`` the vocabulary's
rows held; what absent chips would add is left out, as in the program.

The tree it makes has the layout the program's flax module reads
(``block_<i>/attn/q_down`` ...): names, not values. ``quant`` is the control's
hook (``lowprec.Rounding``): it rounds both operands of every product the
program takes in bfloat16 (every projection's, expert's and attention's, and
``x~ Phi`` of the streams' maps) and the gradient that comes back into it;
the router's product, the maps' sigmoids, Sinkhorn and the mixing of the
streams stay float32, as the program has them.
"""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 1024      # rows of attention scores held at once


def _sizes(cfg):
    return dict(
        d=cfg['hidden_size'], n=cfg['hc_mult'], v=cfg['vocab_size'],
        h=cfg['num_attention_heads'], q_rank=cfg['q_lora_rank'],
        kv_rank=cfg['kv_lora_rank'], nope=cfg['qk_nope_head_dim'],
        rope=cfg['qk_rope_head_dim'], vd=cfg['v_head_dim'],
        f=cfg['intermediate_size'], fe=cfg['moe_intermediate_size'],
        held=cfg['n_routed_experts'],
        experts=cfg['published']['n_routed_experts'],
        shared=cfg['n_shared_experts'])


def layer_kinds(cfg):
    """``'dense'`` for the first ``first_k_dense_replace`` layers, ``'moe'``
    after."""
    return ['dense' if i < cfg['first_k_dense_replace'] else 'moe'
            for i in range(cfg['num_hidden_layers'])]


def _hc_shapes(prefix, s):
    n, nd = s['n'], s['n'] * s['d']
    return {prefix + ('norm', 'scale'): (nd,),
            prefix + ('phi_pre',): (nd, n), prefix + ('phi_post',): (nd, n),
            prefix + ('phi_res',): (nd, n * n),
            prefix + ('b_pre',): (n,), prefix + ('b_post',): (n,),
            prefix + ('b_res',): (n, n),
            prefix + ('alpha_pre',): (), prefix + ('alpha_post',): (),
            prefix + ('alpha_res',): ()}


def _block_shapes(b, kind, s):
    d, h = s['d'], s['h']
    shapes = {}
    shapes.update(_hc_shapes((b, 'attn_hc'), s))
    shapes.update(_hc_shapes((b, 'ffn_hc'), s))
    shapes[(b, 'attn_norm', 'scale')] = (d,)
    shapes[(b, 'ffn_norm', 'scale')] = (d,)
    a = (b, 'attn')
    shapes[a + ('q_down', 'kernel')] = (d, s['q_rank'])
    shapes[a + ('q_norm', 'scale')] = (s['q_rank'],)
    shapes[a + ('q_up', 'kernel')] = (s['q_rank'], h, s['nope'] + s['rope'])
    shapes[a + ('kv_down', 'kernel')] = (d, s['kv_rank'] + s['rope'])
    shapes[a + ('kv_norm', 'scale')] = (s['kv_rank'],)
    shapes[a + ('kv_up', 'kernel')] = (s['kv_rank'], h, s['nope'] + s['vd'])
    shapes[a + ('out', 'kernel')] = (h, s['vd'], d)
    if kind == 'dense':
        for name, shape in (('gate', (d, s['f'])), ('up', (d, s['f'])),
                            ('down', (s['f'], d))):
            shapes[(b, 'mlp', name, 'kernel')] = shape
    else:
        m, fs = (b, 'moe'), s['shared'] * s['fe']
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
    for i, kind in enumerate(layer_kinds(cfg)):
        shapes.update(_block_shapes('block_{}'.format(i), kind, s))
    for i in range(cfg['num_nextn_predict_layers']):
        m = 'mtp_{}'.format(i)
        shapes[(m, 'h_norm', 'scale')] = (d,)
        shapes[(m, 'e_norm', 'scale')] = (d,)
        shapes[(m, 'eh_proj', 'kernel')] = (2 * d, d)
        shapes.update({(m,) + path: shape for path, shape in
                       _block_shapes('block', 'moe', s).items()})
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
    n = cfg['hc_mult']
    a = cfg['assumed']

    @jax.jit
    def make(key):
        flat = {}
        for i, (path, shape) in enumerate(sorted(shapes.items())):
            k = jax.random.fold_in(key, i)
            last = path[-1]
            if last == 'embedding':
                flat[path] = jax.random.normal(k, shape, jnp.float32)
            elif last == 'kernel' or last.startswith(('experts_', 'phi_')):
                flat[path] = 0.02 * jax.random.normal(k, shape, jnp.float32)
            elif last.startswith('alpha_'):
                flat[path] = jnp.full(shape, a['hc_alpha_init'], jnp.float32)
            elif last == 'b_res':
                flat[path] = a['hc_res_diagonal_init'] * jnp.eye(
                    n, dtype=jnp.float32)
            elif last in ('b_pre', 'b_post'):
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


def sinkhorn(logits, iters, eps):
    """``[..., n, n]`` -> doubly stochastic: rows, then columns, ``iters``
    times."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def stream_maps(p, x, cfg, quant=None):
    """``x [B, T, n, d]`` -> ``(H_pre [B, T, n], H_post [B, T, n], H_res [B,
    T, n, n])`` of one sub-layer."""
    b, t, n, d = x.shape
    flat = _rms(x.reshape(b, t, n * d), p['norm']['scale'], cfg['hc_eps'])

    def maps(name):
        return _mm('btk,km->btm', flat, p['phi_' + name], quant) \
            * p['alpha_' + name]

    pre = jax.nn.sigmoid(maps('pre') + p['b_pre'])
    post = 2.0 * jax.nn.sigmoid(maps('post') + p['b_post'])
    res = maps('res').reshape(b, t, n, n) + p['b_res']
    res = jnp.clip(res, cfg['mhc_h_res_clamp_min'], cfg['mhc_h_res_clamp_max'])
    return pre, post, sinkhorn(res, cfg['hc_sinkhorn_iters'], cfg['hc_eps'])


def _sub_layer(p_hc, p_norm, x, fn, cfg, quant=None):
    pre, post, res = stream_maps(p_hc, x, cfg, quant)
    inner = jnp.einsum('btn,btnd->btd', pre, x, precision=HIGHEST)
    y = fn(_rms(inner, p_norm['scale'], cfg['rms_norm_eps']))
    return jnp.einsum('btij,btjd->btid', res, x, precision=HIGHEST) \
        + post[..., None] * y[:, :, None, :]


def yarn_inv_freq(cfg):
    """The rotary frequencies as DeepSeek-V3's yarn embedding makes them:
    below ``low`` the published ones, above ``high`` those over ``factor``,
    a linear ramp between."""
    r, dim, base = cfg['rope_scaling'], cfg['qk_rope_head_dim'], cfg['rope_theta']
    exponents = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / base ** exponents
    inter = 1.0 / (r['factor'] * base ** exponents)

    def correction(rotations):
        return dim * math.log(r['original_max_position_embeddings']
                              / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction(r['beta_fast'])), 0)
    high = min(math.ceil(correction(r['beta_slow'])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 0.001), 0, 1)
    return inter * ramp + extra * (1 - ramp)


def softmax_scale(cfg):
    r = cfg['rope_scaling']
    m = 0.1 * r['mscale_all_dim'] * math.log(r['factor']) + 1.0
    return (cfg['qk_nope_head_dim'] + cfg['qk_rope_head_dim']) ** -0.5 * m * m


def _rotate(x, cfg):
    """Interleaved pairs ``(x[2i], x[2i+1])`` of the last axis turned by
    ``position * frequency_i``; ``x [B, T, ..., rope]``."""
    t = x.shape[1]
    angles = jnp.asarray(np.arange(t)[:, None] * yarn_inv_freq(cfg)[None, :],
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
    c_q = _rms(_mm('btd,dr->btr', x, p['q_down']['kernel'], quant),
               p['q_norm']['scale'], eps)
    q = _mm('btr,rhk->bthk', c_q, p['q_up']['kernel'], quant)
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
        _attend, scale=softmax_scale(cfg), quant=quant))(xs[0], k, v, xs[1]),
        (blocks, rows * jnp.arange(t // rows)))
    out = jnp.moveaxis(out, 0, 1).reshape(v.shape)
    return _mm('bthk,hkd->btd', out, p['out']['kernel'], quant)


def _swiglu(x, gate, up, down, quant):
    hidden = jax.nn.silu(_mm('btd,df->btf', x, gate, quant)) \
        * _mm('btd,df->btf', x, up, quant)
    return _mm('btf,fd->btd', hidden, down, quant)


def route(p, x, cfg):
    """``(experts [B, T, k], weights [B, T, k])``: the published experts each
    token goes to and what each one's output is weighted by."""
    scores = jax.nn.sigmoid(jnp.einsum('btd,de->bte', x, p['router']['kernel'],
                                       precision=HIGHEST))
    _, experts = lax.top_k(scores, cfg['num_experts_per_tok'])  # bias: zeros
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


def _block(p, x, kind, cfg, quant):
    x = _sub_layer(p['attn_hc'], p['attn_norm'], x, functools.partial(
        _attention, p['attn'], cfg=cfg, quant=quant), cfg, quant)
    if kind == 'dense':
        m = p['mlp']
        ffn = functools.partial(_swiglu, gate=m['gate']['kernel'],
                                up=m['up']['kernel'], down=m['down']['kernel'],
                                quant=quant)
    else:
        ffn = functools.partial(_experts, p['moe'], cfg=cfg, quant=quant)
    return _sub_layer(p['ffn_hc'], p['ffn_norm'], x, ffn, cfg, quant)


def _streams(h, n):
    return jnp.broadcast_to(h[:, :, None, :], h.shape[:2] + (n,) + h.shape[2:])


def all_logits(params, tokens, cfg, quant=None):
    """``tokens`` int32 [B, T + 1] -> float32 logits of the next token and of
    the second next, ``[B, T, rows held]`` each (a list: one entry where the
    configuration has no next-token module)."""
    eps, n = cfg['rms_norm_eps'], cfg['hc_mult']
    embedding = params['embed']['embedding']

    def head(h):
        return _mm('btd,dv->btv', _rms(h, params['final_norm']['scale'], eps),
                   params['head']['kernel'], quant)

    x = _streams(embedding[tokens[:, :-1]], n)
    for i, kind in enumerate(layer_kinds(cfg)):
        x = jax.checkpoint(functools.partial(
            _block, kind=kind, cfg=cfg, quant=quant))(
                params['block_{}'.format(i)], x)
    h = jnp.sum(x, axis=2)
    out = [head(h)]
    for i in range(cfg['num_nextn_predict_layers']):
        p = params['mtp_{}'.format(i)]
        both = jnp.concatenate(
            [_rms(h, p['h_norm']['scale'], eps),
             _rms(embedding[tokens[:, 1:]], p['e_norm']['scale'], eps)], axis=-1)
        x = _streams(_mm('btk,kd->btd', both, p['eh_proj']['kernel'], quant), n)
        x = jax.checkpoint(functools.partial(
            _block, kind='moe', cfg=cfg, quant=quant))(p['block'], x)
        h = jnp.sum(x, axis=2)
        out.append(head(h))
    return out


def _cross_entropy(z, targets):
    logp = z - jax.scipy.special.logsumexp(z, axis=-1, keepdims=True)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def _loss(params, tokens, cfg, quant, positions):
    """Next-token mean over the first ``positions`` positions, plus the
    weight times the second-next-token mean over those of them that have a
    second next token in the row."""
    z = all_logits(params, tokens, cfg, quant)
    rows, t = tokens.shape[0], tokens.shape[1] - 1
    total = jnp.sum(_cross_entropy(z[0], tokens[:, 1:])[:, :positions]) \
        / (rows * positions)
    for depth, z_next in enumerate(z[1:], start=2):
        valid = min(positions, t + 1 - depth)
        targets = jnp.pad(tokens[:, depth:], ((0, 0), (0, depth - 1)))
        total = total + cfg['assumed']['mtp_loss_weight'] * jnp.sum(
            _cross_entropy(z_next, targets)[:, :valid]) / (rows * valid)
    return total


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


def _attention_flops(cfg):
    """One attention sub-layer's forward on one row: the five projections,
    and a causal head's two products by the half that is kept (``T T 192``
    for the scores, ``T T 128`` for the values)."""
    s, t = _sizes(cfg), cfg['assumed']['sequence_length']
    qk = s['nope'] + s['rope']
    weights = s['d'] * s['q_rank'] + s['q_rank'] * s['h'] * qk \
        + s['d'] * (s['kv_rank'] + s['rope']) \
        + s['kv_rank'] * s['h'] * (s['nope'] + s['vd']) + s['h'] * s['vd'] * s['d']
    return t * 2 * weights + s['h'] * t * t * (qk + s['vd'])


def _streams_flops(cfg):
    """One sub-layer's stream maps on one row: the three products with Phi
    and the three mixings of the streams."""
    s, t = _sizes(cfg), cfg['assumed']['sequence_length']
    n, d = s['n'], s['d']
    return t * 2 * (n * d * (2 * n + n * n) + d * (2 * n + n * n))


def forward_flops_per_row(cfg):
    """A row is one sequence of ``sequence_length`` positions; what is held
    here only; the routed experts at their expected pairs. Matrix products: 2
    operations a multiply-add."""
    s, t = _sizes(cfg), cfg['assumed']['sequence_length']
    d = s['d']
    dense = t * 2 * 3 * d * s['f']
    moe = t * 2 * (3 * d * s['shared'] * s['fe'] + d * s['experts']) \
        + expected_pairs_per_row(cfg) * 2 * 3 * d * s['fe']
    block = _attention_flops(cfg) + 2 * _streams_flops(cfg)
    nextn = cfg['num_nextn_predict_layers']
    total = (1 + nextn) * t * 2 * d * s['v']
    for kind in layer_kinds(cfg):
        total += block + (dense if kind == 'dense' else moe)
    total += nextn * (block + moe + t * 2 * 2 * d * d)
    return total


def train_flops_per_row(cfg):
    """Forward and backward; what recomputation runs again is not counted."""
    return 3 * forward_flops_per_row(cfg)


def kernels(cfg, rows_per_chip, moe_pairs_per_step=None):
    """The kernels' work in one train step on one chip.

    ``moe``: events named ``moe*`` in the device trace, the grouped products
    of the experts held (``ops.grouped_matmul``): for the pairs routed to
    them in a step, summed over the expert layers (``moe_pairs_per_step``,
    what the step's ``expert_load`` counts; ``None``: the expectation, 256
    an expert a layer a row), the two products forward (``[P, d] x [d, 2
    f]``, ``[P, f] x [f, d]``), the same again where the block is
    recomputed, and their four gradient products; time and count cover the
    same events. Bytes, every array once in bfloat16 a product: the rows read
    and written and the held experts' weights (read by a product, written by
    a weights' gradient). What the capacity's empty tiles cost (zeros
    written) is in the time and not in the count.

    ``flash``: events named ``attn*``, counted as the GPT-2 configuration
    counts them (forward two products, backward five, ``2 T T w`` each,
    halved by the mask: four of the key width 192, three of the value width
    128; q, k and their gradients 192 wide, v, o and theirs 128, once each
    in bfloat16)."""
    s, t = _sizes(cfg), cfg['assumed']['sequence_length']
    d, fe, held = s['d'], s['fe'], s['held']
    layers = (layer_kinds(cfg).count('moe')
              + cfg['num_nextn_predict_layers']) * rows_per_chip
    pairs = layers * expected_pairs_per_row(cfg) \
        if moe_pairs_per_step is None else moe_pairs_per_step
    passes = 2 if cfg['assumed']['recompute_each_layer'] else 1
    product = pairs * 2 * 3 * d * fe                    # one forward
    rows_moved = pairs * (d + 2 * fe) + pairs * (fe + d)  # in and out, both
    weights = layers * held * 3 * d * fe
    moe = {'match': '^moe',
           'flops': (passes + 2) * product,
           'bytes': (passes + 2) * 2 * (rows_moved + weights)}
    blocks = (cfg['num_hidden_layers']
              + cfg['num_nextn_predict_layers']) * rows_per_chip
    qk, vd = s['nope'] + s['rope'], s['vd']
    flash = {'match': '^attn',
             'flops': blocks * s['h'] * (4 * qk + 3 * vd) * t * t,
             'bytes': blocks * s['h'] * t * 4 * (qk + vd) * 2}
    return {'moe': moe, 'flash': flash}
