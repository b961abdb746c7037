"""Pure-jnp oracle for the SSD scan kernels: the model-level chunked SSD of
``repro.models.ssm``, in the same (B, L, H, P) layout."""
from repro.models.ssm import ssd_chunked_jnp as ssd_scan_ref  # noqa: F401
