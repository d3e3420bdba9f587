"""Device seconds of one Mosaic kernel inside the executions of one program,
found in the trace by its name: a Pallas call is a custom call to
`tpu_custom_call` that XLA names after the `jax.named_scope` it was made in,
and the trace's key of an operation is that name, the opcode and the shape of
the first result (xplane.parse_instruction), e.g.
`latent_paged_attention_custom-call_bf16_32_64_512_` for the latent decode
read of 32 slots, 64 heads and 512 values (the shape tells the decode read
from the lanes' read, which is the same kernel). A program that has no such
call (the parent of the PR that added the kernel) gives an empty list."""

from __future__ import annotations

import bisect


def kernel_key(name: str, dtype: str, shape) -> str:
    """The trace's key of the Mosaic call made in scope `name` whose first
    result has this dtype and shape."""
    short = {"bfloat16": "bf16", "float32": "f32"}[dtype]
    return (f"{name}_custom-call_{short}_"
            + "_".join(str(int(d)) for d in shape) + "_")


def per_execution_seconds(trace, key: str, module=None):
    """For each execution of `module` (the main one when None) on chip 0 in
    which a Mosaic call with result `key` ran: (seconds they took together,
    how many ran)."""
    if trace is None or not trace.devices:
        return []
    dev = trace.devices[0]
    module = module or trace.main_module()
    calls = [(s, e) for s, e, k, _, mosaic in dev.ops if mosaic and k == key]
    starts = [s for s, _ in calls]
    out = []
    for s, e, name, _ in dev.modules:
        if name != module:
            continue
        i, j = bisect.bisect_left(starts, s), bisect.bisect_left(starts, e)
        if j > i:
            out.append((sum(b - a for a, b in calls[i:j]), j - i))
    return out
