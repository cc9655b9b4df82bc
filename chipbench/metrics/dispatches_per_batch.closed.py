"""Device programs launched through repro.kernels per update_batch call:
the program's kernels.dispatches counter over the window's calls."""
from chipbench import layers

read = layers.dispatches_per_batch
