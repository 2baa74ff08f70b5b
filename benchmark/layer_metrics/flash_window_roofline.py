"""The banded flash-attention call against its roofline: the least time the
chip could take for the windowed calls of the traced window (FLOP over the
band's query-key pairs, bytes of q and the output at the heads' count and of
K and V at the kv heads', ``counts/afmoe.py``'s ``band_flash_call``) over the
device time of the kernel's events. The kernel's events are told from others
by the custom call's own name, ``flash_window_forward``
(ops/flash_attention.py: ``pallas_call(name=...)``); the full causal call,
``flash_forward``, is ``flash_roofline``'s. Nothing where the window holds no
such call, or the configuration's counts know no band."""

from benchmark import loader
from benchmark.measure import kernel_roofline

# The kernel's output, bf16[rows x heads, positions, head size]; compiled for
# the chip here: ``%flash_window_forward.1 = bf16[48,12288,128]{...}
# custom-call(...)`` for one prompt at 48 heads (PR 33).
OUTPUT = r"=\s*bf16\[(?P<rows_heads>\d+),(?P<positions>\d+),(?P<head_size>\d+)\]"


def read(run):
    try:
        price = loader.counts_of(run.hf).band_flash_call
    except (KeyError, AttributeError, FileNotFoundError):
        return None  # a configuration that names no counts, or none with a band
    return kernel_roofline(
        run, "flash_window_forward", OUTPUT,
        lambda rows_heads, positions, head_size: price(
            rows_heads, positions, head_size, run.hf))
