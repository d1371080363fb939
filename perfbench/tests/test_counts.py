"""Operation, byte and parameter counts from the shapes, against hand counts."""

import json
import os

from conftest import PERFBENCH

from perfbench import harness


def _load(name):
    cfg = json.load(open(os.path.join(PERFBENCH, 'configs', name + '.json')))
    ref = harness.load_module(os.path.join(PERFBENCH, 'configs',
                                           name + '.reference.py'))
    return cfg, ref


def test_resnet50_forward_is_about_8_gflop_an_image():
    cfg, ref = _load('resnet50-imagenet224')
    # By hand: the stem, 7x7x3x64 at 112x112, is 2*147*64*12544 = 0.236 G;
    # the head 2*2048*1000 = 4.1 M; torchvision's count for the v1.5 network
    # is 4.09 G multiply-adds, twice that in operations.
    forward = ref.forward_flops_per_row(cfg)
    assert 2 * 147 * 64 * 112 * 112 == 236027904
    assert abs(forward - 2 * 4.09e9) / (2 * 4.09e9) < 0.01
    assert ref.train_flops_per_row(cfg) == 3 * forward


def test_resnet50_has_25_6_m_parameters():
    cfg, ref = _load('resnet50-imagenet224')
    count = sum(int(__import__('numpy').prod(s))
                for s in ref.param_shapes(cfg).values())
    assert count == cfg['parameters'] == 25557032


def test_gpt2_small_counts():
    cfg, ref = _load('gpt2-small-ctx1024')
    d, layers, vocab, t = 768, 12, 50257, 1024
    block = 4 * d * d + 4 * d + 2 * 4 * d * d + 4 * d + d + 4 * d   # weights, biases, norms
    by_hand = vocab * d + t * d + layers * block + 2 * d + d * vocab + vocab
    count = sum(int(__import__('numpy').prod(s))
                for s in ref.param_shapes(cfg).values())
    assert count == by_hand == cfg['parameters']
    # Forward, a token: 12 blocks of 24*d*d plus the head 2*d*vocab; a causal
    # head's two products touch half of T*T: 2*T*64 a token, layer and head.
    per_token = layers * 24 * d * d + 2 * d * vocab
    attention = layers * 12 * 2 * t * 64
    assert ref.forward_flops_per_row(cfg) == t * (per_token + attention)
    flash = ref.kernels(cfg, 16)['flash']
    # 7 products (2 forward, 5 backward) of 2*T*T*64, halved, over
    # 16 rows x 12 layers x 12 heads: 1.08 TFLOP a step; 8 tensors of bf16.
    assert flash['flops'] == 16 * 144 * 7 * t * t * 64
    assert flash['bytes'] == 16 * 144 * 8 * t * 64 * 2
    # compute-bound at these shapes: 5.5 ms of MXU against 2.9 ms of HBM
    assert flash["flops"] / 197e12 > flash["bytes"] / 819e9
