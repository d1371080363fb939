"""ViT model family (``petastorm_tpu/models/vit.py``): forward contract,
bidirectional attention, reader-fed training, and tensor parallelism via the
shared ``transformer_param_spec``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from petastorm_tpu.models import ViT, ViTTiny
from petastorm_tpu.models.train import (create_train_state, make_train_step,
                                        transformer_param_spec)
from petastorm_tpu.parallel import make_mesh


# Heavyweight (jit compiles of full models / interpret-mode Pallas):
# excluded from the fast CI lane; run the full suite before shipping.
pytestmark = pytest.mark.slow

def test_forward_shape_and_dtype():
    model = ViTTiny(num_classes=7)
    x = jnp.ones((2, 16, 16, 3), jnp.float32)
    params = model.init(jax.random.PRNGKey(0), x)['params']
    logits = model.apply({'params': params}, x)
    assert logits.shape == (2, 7) and logits.dtype == jnp.float32


def test_indivisible_patch_raises():
    model = ViTTiny(num_classes=2)   # patch 4
    x = jnp.ones((1, 18, 16, 3), jnp.float32)
    with pytest.raises(ValueError, match='not divisible'):
        model.init(jax.random.PRNGKey(0), x)


def test_attention_is_bidirectional():
    """A causal stack cannot let early patches see late ones; ViT must.
    Changing ONLY the last patch must move the CLS logits (CLS is position
    0 — under causal masking it would be blind to every patch)."""
    model = ViTTiny(num_classes=3)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, 16, 16, 3)), jnp.float32)
    params = model.init(jax.random.PRNGKey(1), x)['params']
    base = model.apply({'params': params}, x)
    bumped = x.at[:, 12:, 12:, :].add(3.0)   # last patch rows/cols only
    moved = model.apply({'params': params}, bumped)
    assert not np.allclose(np.asarray(base), np.asarray(moved))


def test_trains_from_reader(tmp_path):
    from petastorm_tpu import make_tensor_reader
    from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.jax_loader import JaxLoader
    from petastorm_tpu.unischema import Unischema, UnischemaField

    schema = Unischema('V', [
        UnischemaField('image', np.uint8, (16, 16, 3),
                       CompressedImageCodec('png'), False),
        UnischemaField('label', np.int64, (), ScalarCodec(np.int64), False)])
    rng = np.random.default_rng(5)
    url = 'file://' + str(tmp_path / 'ds')
    write_dataset(url, schema,
                  ({'image': rng.integers(0, 255, (16, 16, 3), dtype=np.uint8),
                    'label': int(i % 3)} for i in range(32)),
                  rows_per_row_group=8)

    model = ViTTiny(num_classes=3)
    state = create_train_state(jax.random.PRNGKey(0), model, (1, 16, 16, 3))
    step = make_train_step()
    with make_tensor_reader(url, num_epochs=1, seed=0) as reader:
        with JaxLoader(reader, 8, last_batch='drop') as loader:
            for batch in loader:
                state, metrics = step(
                    state, batch.image.astype('float32') / 255.0, batch.label)
    assert np.isfinite(float(metrics['loss']))


def test_tensor_parallel_sharding_applies():
    mesh = make_mesh({'data': 4, 'model': 2})
    model = ViTTiny(num_classes=4)
    state = create_train_state(jax.random.PRNGKey(0), model, (1, 16, 16, 3),
                               mesh=mesh, param_spec_fn=transformer_param_spec)
    # The shared Megatron spec must actually shard the blocks' q/k/v and MLP.
    p = state.params
    qkv = p['block_0']['attn']['query']['kernel']
    up = p['block_0']['Dense_0']['kernel']
    assert 'model' in str(qkv.sharding.spec)
    assert 'model' in str(up.sharding.spec)
    # And a sharded train step runs.
    step = make_train_step(mesh=mesh)
    x = jnp.ones((8, 16, 16, 3), jnp.float32)
    y = jnp.zeros((8,), jnp.int32)
    state, metrics = step(state, x, y)
    assert np.isfinite(float(metrics['loss']))


def test_flash_kernel_handles_vit_sequence_length():
    """ViT's sequence is patches+CLS = a NON-block-aligned length (e.g. 65).
    Exercise the Pallas kernel (interpret=True: the compiled kernel needs
    a TPU) non-causally at exactly that shape against the dense
    reference."""
    from petastorm_tpu.models.attention import dense_attention
    from petastorm_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(2)
    t = (32 // 4) * (32 // 4) + 1   # 65: ViT 32x32 / patch 4 + CLS
    shape = (2, t, 2, 16)           # [B, T, H, D] — T must be the 65
    q = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    k = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    v = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    out_f = flash_attention(q, k, v, causal=False, interpret=True)
    out_d = dense_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d),
                               rtol=2e-3, atol=2e-3)


def test_flash_backend_forward_runs():
    """The module-level flash path (the Pallas kernel, in the interpreter
    on this CPU) produces finite logits at ViT shapes."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 32, 32, 3)), jnp.float32)
    flash = ViT(num_classes=5, patch_size=4, d_model=32, num_heads=2,
                num_layers=1, attention='flash:interpret', dtype=jnp.float32)
    params = flash.init(jax.random.PRNGKey(3), x)['params']
    out = flash.apply({'params': params}, x)
    assert out.shape == (2, 5) and np.isfinite(np.asarray(out)).all()
