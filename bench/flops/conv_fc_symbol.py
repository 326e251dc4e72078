"""Required forward operations per sample of a convolutional symbol,
from shapes alone: every Convolution and FullyConnected node, two
operations per multiply-add, bias adds included. Training is three
times this (forward, and backward for data and for weights); recomputed
operations never count.

The arithmetic is a copy of ``mxnet_tpu.telemetry.costmodel
.analytic_forward_flops`` (PR 5) made on the day of PR 22; the tests
hold the two equal to 0.1% and each to the published counts. It walks
the symbol's public JSON and ``infer_shape`` only.
"""
from __future__ import annotations

import json

import lib

TRAIN_MULTIPLIER = 3


def forward_flops(symbol, **input_shapes):
    """Forward operations for one batch of ``input_shapes``."""
    graph = json.loads(symbol.tojson())
    internals = symbol.get_internals()
    _, out_shapes, _ = internals.infer_shape(**input_shapes)
    shape_of = dict(zip(internals.list_outputs(), out_shapes))
    nodes = graph["nodes"]
    total = 0.0
    for node in nodes:
        if node["op"] not in ("Convolution", "FullyConnected"):
            continue
        n_out = 1
        for d in shape_of[node["name"] + "_output"]:
            n_out *= int(d)
        # input 1 is the weight: (out, in/groups, kh, kw) or (out, in), so
        # each output element reduces over everything past its first axis
        weight = shape_of[nodes[node["inputs"][1][0]]["name"]]
        reduce_len = 1
        for d in weight[1:]:
            reduce_len *= int(d)
        total += 2.0 * n_out * reduce_len
        if str(node["attr"].get("no_bias", "False")) != "True":
            total += float(n_out)
    return total


def forward_flops_per_sample(cfg):
    symbol = lib.resolve(cfg["factory"])(**cfg["kwargs"])
    return forward_flops(symbol, data=(1,) + tuple(cfg["input_shape"]))
