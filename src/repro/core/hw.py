"""Target hardware constant (TPU v5e) for the planner's compute-time model."""

# bf16 FLOP/s of one v5e chip (Google Cloud TPU v5e documentation; the
# benchmark's peaks.json carries the same figure)
PEAK_FLOPS_BF16 = 197e12
