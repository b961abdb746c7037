from repro.kernels.causal_conv.ops import (  # noqa: F401
    causal_conv, causal_conv_skip, fits)
