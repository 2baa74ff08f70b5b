"""The Mamba-2 decode-state kernel against its roofline: the least time the
chip could take for the calls of the traced window over the device time of
the kernel's events. The kernel's events are told from others by the custom
call's own name, ``ssm_step`` (ops/ssm_step.py: ``pallas_call(name=...)``),
and no other Pallas call is counted; where none is found the reader returns
nothing (a decoder without a recurrent state, or a program without the
kernel).

A call steps one plane of the stacked state ``h`` (rows x heads x head size x
state, float32): it reads the plane and writes it back (8 B an element), and
reads ``decay`` (spread over the head size, as the kernel takes it), ``dt
x`` and ``B`` and ``C`` (a row's groups x state) and writes ``y`` (rows x
heads x head size), all float32. FLOP: 5 a state element, as
``counts/nemotron_h.recurrence_flops`` counts the recurrence (the update's
multiply, multiply and add, the readout's multiply-add). The groups of ``B``
and ``C`` are not in the call's output shapes: they come from the
configuration's file (``n_groups``, or ``mamba_n_groups``)."""

from benchmark.measure import kernel_roofline

# The kernel's outputs: the stacked state (aliased to its input) and y;
# compiled for a v5e here: ``%ssm_step.1 = (f32[9,64,128,64,128]{4,3,2,1,0:
# T(8,128)}, f32[64,128,64]{...}) custom-call(...)`` for Granite's nine
# mamba layers at 64 rows.
OUTPUT = (r"=\s*\(f32\[(?P<planes>\d+),(?P<rows>\d+),(?P<heads>\d+),"
          r"(?P<head_size>\d+),(?P<state>\d+)\]")


def price(planes: int, rows: int, heads: int, head_size: int, state: int,
          groups: int) -> dict:
    """One call: one plane stepped."""
    plane = rows * heads * head_size * state
    small = 3 * rows * heads * head_size + 2 * rows * groups * state
    return {"flop": 5.0 * plane, "bytes": 4.0 * (2 * plane + small)}


def read(run):
    groups = run.hf.get("n_groups", run.hf.get("mamba_n_groups"))
    if groups is None:
        return None  # no recurrent layer in this configuration
    return kernel_roofline(
        run, "ssm_step", OUTPUT,
        lambda **shape: price(groups=int(groups), **shape))
