"""FLOP and byte counts of both configurations, against hand counts."""

import json

import _pb
from perfbench.models import lstm_autoencoder as ae


def cfg(name):
    return json.loads(
        (_pb.ROOT / "perfbench" / "configs" / f"{name}.json").read_text())


def test_layer_dims_follow_the_published_widths():
    assert ae.layer_dims(cfg("gw_nominal")) == [(1, 32), (32, 8), (8, 8),
                                                (8, 32)]
    assert ae.layer_dims(cfg("gw_small")) == [(1, 9), (9, 9)]


def test_model_flops_per_window():
    # per step, per cell: 2*H*4H recurrent + 2*D*4H input + 13 H elementwise
    nominal = (
        (2 * 32 * 128 + 2 * 1 * 128 + 13 * 32)    # 8864
        + (2 * 8 * 32 + 2 * 32 * 32 + 13 * 8)     # 2664
        + (2 * 8 * 32 + 2 * 8 * 32 + 13 * 8)      # 1128
        + (2 * 32 * 128 + 2 * 8 * 128 + 13 * 32)  # 10656
        + (2 * 32 + 1) + 3)                       # head + squared error
    assert nominal == 23380
    assert ae.model_flops(cfg("gw_nominal")) == 100 * 23380
    small = ((2 * 9 * 36 + 2 * 36 + 13 * 9) + (2 * 9 * 36 * 2 + 13 * 9)
             + (2 * 9 + 1) + 3)
    assert small == 2272
    assert ae.model_flops(cfg("gw_small")) == 100 * 2272


def test_kernel_work_counts_the_segment_at_published_widths():
    r, c, k = 2500, 100, 3  # row-steps, rows over calls, calls
    flops, nbytes = ae.kernel_work(cfg("gw_nominal"), "encoder", r, c, k)
    # layer 0 without its input projection, layer 1 whole
    assert flops == r * ((2 * 32 * 128 + 13 * 32)
                         + (2 * 8 * 32 + 2 * 32 * 32 + 13 * 8))
    # per row-step: 4*32 gates in, 8 out; per row-call: (h, c) of 32 and 8,
    # in and out; per call: layer 0 w_h + b, layer 1 w_x + w_h + b
    floats = (r * (128 + 8) + c * 2 * 2 * 40
              + k * ((32 * 128 + 128) + (32 * 32 + 8 * 32 + 32)))
    assert nbytes == 4 * floats
    flops, nbytes = ae.kernel_work(cfg("gw_small"), "decoder", r, c, k)
    assert flops == r * (2 * 9 * 36 + 13 * 9)
    assert nbytes == 4 * (r * (36 + 9) + c * 2 * 2 * 9 + k * (9 * 36 + 36))
