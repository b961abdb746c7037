"""Pure-jnp oracles for the causal conv kernels: the model's own
``_causal_conv`` and ``_causal_conv_skip`` of ``repro.models.ssm``, in the
same (B, L, C) layout."""
from repro.models.ssm import _causal_conv as causal_conv_ref  # noqa: F401
from repro.models.ssm import (  # noqa: F401
    _causal_conv_skip as causal_conv_skip_ref)
