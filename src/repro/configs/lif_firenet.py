"""LIF-FireNet: a recurrent spiking network for event-based optical flow.

Hagenaars, Paredes-Vallés and de Croon, "Self-Supervised Learning of
Event-Based Optical Flow with Spiking Neural Networks", NeurIPS 2021
(arXiv:2106.01862; code: github.com/tudelft/event_flow, ``LIFFireNet``).
At its published width (``base_num_channels`` 32, 3x3 kernels, ON/OFF
input channels), every conv 3x3, stride 1, padding 1 unless noted:

  0 head  ConvLIF 2->32
  1 G1    ConvLIFRecurrent 32->32     (input joined to its own z(t-1))
  2 R1a   ConvLIF 32->32
  3 R1b   ConvLIF 32->32
  4 G2    ConvLIFRecurrent 32->32
  5 R2a   ConvLIF 32->32
  6 R2b   ConvLIF 32->32
  7 pred  1x1 conv 32->2, no neuron (flow = tanh of it; the readout here
          is the saturated integer current, tanh left out)

Each layer is fed the spikes of the one before it: ``FireNet.forward``
adds a residual to R1b and R2b only ``if self.residual``, and
``LIFFireNet`` sets ``residual = False``.

event_flow's neuron, ``v = leak * v * (1 - z) + (1 - leak) * I`` with
reset by the last spike, is the integer LIF with hard reset: ``1 - leak``
folds into the weights and the leak is ``v - (v >> leak_shift)``.  The
trained per-channel leaks and thresholds are not in this repository;
``leak_shift`` 1 is the shift nearest the initial ``sigmoid(-4)``.
"""
from ..core.layers import SpikingConvParams
from ..core.network import SNNLayer, SNNSpec
from ..core.neuron import NeuronConfig

__all__ = ["CONFIG", "TINY", "lif_firenet", "reduced"]


def lif_firenet(channels: int = 32, neuron: NeuronConfig | None = None,
                input_hw: tuple = (260, 346),
                timesteps: int = 10) -> SNNSpec:
    """The network at ``channels`` wide (32 published) on ``input_hw``
    frames of two event channels (DAVIS346 by default)."""
    n = neuron or NeuronConfig(model="lif", reset="hard", threshold=0.5,
                               leak_shift=1)

    def conv(c_in, **flags):
        return SNNLayer("conv", c_in, channels,
                        conv=SpikingConvParams(3, 3, 1, 1, n), **flags)

    return SNNSpec(
        name="lif_firenet",
        input_hw=tuple(input_hw),
        in_channels=2,
        timesteps=timesteps,
        layers=(
            conv(2),
            conv(channels, recurrent=True),
            conv(channels),
            conv(channels),
            conv(channels, recurrent=True),
            conv(channels),
            conv(channels),
            SNNLayer("conv", channels, 2,
                     conv=SpikingConvParams(1, 1, 1, 0, n), stateless=True),
        ),
        readout="vmem",
    )


CONFIG = lif_firenet()


def reduced(hw: tuple = (8, 10), timesteps: int = 4,
            channels: int = 4) -> SNNSpec:
    """CPU-sized variant for tests and demos: the same eight layers and
    connections on smaller frames, fewer timesteps and fewer channels."""
    return lif_firenet(channels, input_hw=hw, timesteps=timesteps)


# The CPU test size of the benchmark's tiny configuration
# (``tests/bench/data/tiny-firenet.json``).
TINY = reduced()
