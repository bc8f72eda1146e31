"""TPU v5e hardware constants (the platform this framework targets), used
by the analytical roofline of the LM dry-run (``roofline/analysis.py``)."""

PEAK_FLOPS_BF16 = 197e12      # per chip, bf16
HBM_BW = 819e9                # bytes/s per chip
ICI_BW_PER_LINK = 50e9        # bytes/s per link (~400 Gbps x dirs)
ICI_LINKS = 4                 # torus links usable per chip (2D torus: 4)
VMEM_BYTES = 128 * 1024**2    # ~128 MiB vector memory
HBM_BYTES = 16 * 1024**3      # 16 GiB per chip
MXU_DIM = 128                 # systolic array tile edge
