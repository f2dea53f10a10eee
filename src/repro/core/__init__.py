"""The paper's contribution: BaF tensor compression as composable JAX modules."""
from repro.core.quant import (QuantParams, compute_quant_params, quantize,
                              dequantize, bin_bounds, quantization_mse)
from repro.core.selection import (SelectionResult, correlation_matrix_conv,
                                  correlation_matrix_stream, select_channels,
                                  select_channels_greedy, accumulate_correlation)
from repro.core.losses import charbonnier
from repro.core.baf import (BaFConvConfig, BaFStreamConfig, init_baf_conv,
                            init_baf_stream, baf_conv_predict, baf_stream_predict,
                            baf_conv_backward, baf_conv_forward,
                            baf_stream_backward, consolidate, scatter_consolidated,
                            gather_bn)
from repro.core.split import SplitInferenceEngine, SplitStats
from repro.core import codec
