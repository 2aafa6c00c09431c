"""Pallas TPU kernels (validated with interpret=True on CPU).

stream_matmul    the paper's weight path: HBM-resident weights streamed
                 through a bounded VMEM prefetch ring (burst/FIFO/credits)
conv2d_int8      HPIPE layer engine: line-buffer row conv, int8 MXU dots;
                 ``dwconv_int8``, its depthwise (grouped) engine
pool_int8        the pooling topology engines: line-buffer maxpool
                 (comparator trees) and global-average-pool (int32
                 channel accumulators + activation requantizer)
flash_attention  blockwise online-softmax attention (causal / window /
                 softcap / GQA)
"""
from repro.kernels.stream_matmul.ops import stream_matmul
from repro.kernels.conv2d_int8.ops import (conv2d_int8, conv2d_int8_requant,
                                          dwconv_int8)
from repro.kernels.pool_int8.ops import global_avgpool_int8, maxpool_int8
from repro.kernels.flash_attention.ops import flash_attention

__all__ = ["stream_matmul", "conv2d_int8", "conv2d_int8_requant",
           "dwconv_int8", "maxpool_int8", "global_avgpool_int8",
           "flash_attention"]
