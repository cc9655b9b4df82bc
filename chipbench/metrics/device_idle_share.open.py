"""Share of the traced window in which no operation ran on the device."""
from chipbench import layers

read = layers.device_idle_share
