from repro.kernels.ssd_scan.ops import fits, ssd_scan  # noqa: F401
