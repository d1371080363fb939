"""Plain reference of the ResNet configuration: forward, loss, gradients and
the momentum-SGD update in straightforward ``jax.numpy`` / ``lax.conv`` at
float32 and ``Precision.HIGHEST``. No flax, no kernels, nothing of
``petastorm_tpu``. It also makes the weights (from the seed) and counts the
operations (from the shapes), so the program supplies neither.

The tree it makes has the layout the program's flax module reads
(``conv_init``, ``bn_init``, ``BottleneckBlock_<i>/Conv_<j>`` ...): names, not
values. Departures from He et al. are listed in the configuration's json.

``quant`` is the control's hook (``lowprec.Rounding``): it rounds both
operands of every convolution and matrix product and the gradient that comes
back into it (the reference put in the program's place at a lower precision).
``None`` is the reference itself.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
BN_EPS = 1e-5


# -- shapes ------------------------------------------------------------------

def _blocks(cfg):
    """(name, cin, filters, stride, spatial_in) of every bottleneck."""
    out = []
    size = cfg['image_size'] // 4           # after the stem conv and the pool
    cin = cfg['num_filters']
    index = 0
    for i, count in enumerate(cfg['stage_sizes']):
        filters = cfg['num_filters'] * 2 ** i
        for j in range(count):
            stride = 2 if i > 0 and j == 0 else 1
            out.append(('BottleneckBlock_{}'.format(index), cin, filters,
                        stride, size))
            size //= stride
            cin = filters * cfg['bottleneck_expansion']
            index += 1
    return out


def param_shapes(cfg):
    """path tuple -> shape, in the program's layout."""
    nf, exp = cfg['num_filters'], cfg['bottleneck_expansion']
    shapes = {('conv_init', 'kernel'): (7, 7, cfg['channels'], nf),
              ('bn_init', 'scale'): (nf,), ('bn_init', 'bias'): (nf,)}
    for name, cin, f, _, _ in _blocks(cfg):
        convs = [('Conv_0', (1, 1, cin, f)), ('Conv_1', (3, 3, f, f)),
                 ('Conv_2', (1, 1, f, f * exp))]
        for k, (conv, shape) in enumerate(convs):
            shapes[(name, conv, 'kernel')] = shape
            shapes[(name, 'BatchNorm_{}'.format(k), 'scale')] = (shape[-1],)
            shapes[(name, 'BatchNorm_{}'.format(k), 'bias')] = (shape[-1],)
        if cin != f * exp:
            shapes[(name, 'conv_proj', 'kernel')] = (1, 1, cin, f * exp)
            shapes[(name, 'norm_proj', 'scale')] = (f * exp,)
            shapes[(name, 'norm_proj', 'bias')] = (f * exp,)
    width = cfg['num_filters'] * 2 ** (len(cfg['stage_sizes']) - 1) * exp
    shapes[('head', 'kernel')] = (width, cfg['num_classes'])
    shapes[('head', 'bias')] = (cfg['num_classes'],)
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

    @jax.jit
    def make(key):
        flat = {}
        for n, (path, shape) in enumerate(sorted(shapes.items())):
            if path[-1] == 'kernel':
                fan_in = int(np.prod(shape[:-1]))
                flat[path] = (jax.random.normal(jax.random.fold_in(key, n),
                                                shape, jnp.float32)
                              * np.sqrt(2.0 / fan_in))
            elif path[-1] == 'scale':
                flat[path] = jnp.ones(shape, jnp.float32)
            else:
                flat[path] = jnp.zeros(shape, jnp.float32)
        return _nest(flat)

    return make(seed_key(seed))


def init_batch_stats(cfg):
    """Running statistics the program's BatchNorm keeps (mean 0, var 1):
    state the train step updates and never reads in training mode."""
    flat = {}
    for path, shape in param_shapes(cfg).items():
        if path[-1] == 'scale':
            flat[path[:-1] + ('mean',)] = jnp.zeros(shape, jnp.float32)
            flat[path[:-1] + ('var',)] = jnp.ones(shape, jnp.float32)
    return _nest(flat)


# -- forward -----------------------------------------------------------------

def _conv(x, w, stride, padding, quant):
    if quant is not None:
        x, w = quant.operand(x), quant.operand(w)
    y = lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=('NHWC', 'HWIO', 'NHWC'), precision=HIGHEST)
    return y if quant is None else quant.cotangent(y)


def _bn(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + BN_EPS) * p['scale'] + p['bias']


def _bottleneck(p, x, stride, quant):
    y = jax.nn.relu(_bn(_conv(x, p['Conv_0']['kernel'], 1, 'SAME', quant),
                        p['BatchNorm_0']))
    y = jax.nn.relu(_bn(_conv(y, p['Conv_1']['kernel'], stride, 'SAME', quant),
                        p['BatchNorm_1']))
    y = _bn(_conv(y, p['Conv_2']['kernel'], 1, 'SAME', quant), p['BatchNorm_2'])
    if 'conv_proj' in p:
        x = _bn(_conv(x, p['conv_proj']['kernel'], stride, 'SAME', quant),
                p['norm_proj'])
    return jax.nn.relu(x + y)


def logits(params, images, cfg, quant=None):
    """``images`` uint8 [B, H, W, C] -> float32 logits [B, classes]."""
    x = images.astype(jnp.float32) / 255.0
    x = _conv(x, params['conv_init']['kernel'], 2, [(3, 3), (3, 3)], quant)
    x = jax.nn.relu(_bn(x, params['bn_init']))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          'SAME')
    for name, _, _, stride, _ in _blocks(cfg):
        # Recompute a block's inside in the backward pass: float32
        # activations of 256 images would not fit the chip otherwise. The
        # arithmetic is the same.
        block = jax.checkpoint(functools.partial(_bottleneck, stride=stride,
                                                 quant=quant))
        x = block(params[name], x)
    x = jnp.mean(x, axis=(1, 2))
    head = params['head']
    if quant is not None:
        return quant.cotangent(jnp.dot(
            quant.operand(x), quant.operand(head['kernel']),
            precision=HIGHEST)) + head['bias']
    return jnp.dot(x, head['kernel'], precision=HIGHEST) + head['bias']


def loss(params, inputs, cfg, quant=None):
    """Mean softmax cross-entropy over the rows."""
    z = logits(params, inputs['image'], cfg, quant)
    logp = z - jax.scipy.special.logsumexp(z, axis=-1, keepdims=True)
    labels = inputs['label'].astype(jnp.int32)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def loss_and_grad(params, inputs, cfg, quant=None, rows_used=None):
    """One jitted call; batch-norm ties the rows, so no blocks of rows.
    ``rows_used`` (a fault for the tests and the calibration): only that many
    leading rows enter the mean."""
    if rows_used is not None:
        inputs = {k: v[:rows_used] for k, v in inputs.items()}
    fn = _compiled(_freeze(cfg), quant)
    return fn(params, inputs)


def _freeze(cfg):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()
                        if isinstance(v, (int, float, str, list))))


@functools.lru_cache(maxsize=None)
def _compiled(frozen_cfg, quant):
    cfg = {k: (list(v) if isinstance(v, tuple) else v) for k, v in frozen_cfg}
    return jax.jit(jax.value_and_grad(
        lambda p, i: loss(p, i, cfg, quant)))


# -- optimizer: weight decay into the gradient, then momentum SGD -------------

def opt_init(params, cfg):
    return {'trace': jax.tree_util.tree_map(jnp.zeros_like, params)}


def gradient_as_optimizer_gets_it(grads, params, cfg):
    """What the momentum accumulator holds after the first step."""
    wd = cfg['assumed']['weight_decay']
    return jax.tree_util.tree_map(lambda g, p: g + wd * p, grads, params)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _sgd(params, trace, grads, lr, momentum, wd):
    g = jax.tree_util.tree_map(lambda g, p: g + wd * p, grads, params)
    trace = jax.tree_util.tree_map(lambda g, t: g + momentum * t, g, trace)
    params = jax.tree_util.tree_map(lambda p, t: p - lr * t, params, trace)
    return params, trace


def opt_apply(params, opt, grads, cfg, step):
    a = cfg['assumed']
    params, trace = _sgd(params, opt['trace'], grads, a['learning_rate'],
                         a['momentum'], a['weight_decay'])
    return params, {'trace': trace}


# -- operations, from the shapes -----------------------------------------------

def forward_flops_per_row(cfg):
    """Multiply-adds counted as two operations, convolutions and the head
    only (batch norm, relu, pooling are under 1 %): 8.2 GFLOP an image for
    the 50-layer column at 224x224, twice the 4.1 G multiply-adds that the
    paper's table calls FLOPs."""
    def conv(k, cin, cout, out_size):
        return 2 * k * k * cin * cout * out_size * out_size

    exp = cfg['bottleneck_expansion']
    total = conv(7, cfg['channels'], cfg['num_filters'],
                 cfg['image_size'] // 2)
    for _, cin, f, stride, size in _blocks(cfg):
        out = size // stride
        total += conv(1, cin, f, size) + conv(3, f, f, out)
        total += conv(1, f, f * exp, out)
        if cin != f * exp:
            total += conv(1, cin, f * exp, out)
    width = cfg['num_filters'] * 2 ** (len(cfg['stage_sizes']) - 1) * exp
    return total + 2 * width * cfg['num_classes']


def train_flops_per_row(cfg):
    """Forward plus backward: the backward pass of a convolution is two
    more of its size (one for its input, one for its kernel)."""
    return 3 * forward_flops_per_row(cfg)


def kernels(cfg, rows_per_chip):
    """No hand-written kernel on this configuration's path."""
    return {}
