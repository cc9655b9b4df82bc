"""Host time per update_batch call, ms: each call's span less the device
busy time inside it, averaged over the traced window's calls."""
from chipbench import layers

read = layers.host_ms_per_batch
