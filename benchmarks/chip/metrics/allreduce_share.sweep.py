"""Share of the decode program's device time spent in all-reduce ops, in %: on each chip, the time of the all-reduce ops inside `_decode_fn` calls over the time of those calls, then the mean over the chips.

Under tensor parallelism the decode program reduces across chips after each layer's row-parallel o-projection and down-projection (`attn.out_proj`, `mlp.down_proj` in the ops' metadata) and once after the embedding lookup from the vocabulary-split table: 97 reductions a step at Qwen2.5-14B's 48 layers. On a TPU v5e 2x2 the compiler emits each as one synchronous op, `%all-reduce.N = bf16[...] all-reduce(...)`; an async `all-reduce-start` / `all-reduce-done` pair counts too."""
import re

import numpy as np

from chipbench.readers import DECODE

# The op's own opcode: an operand is written `%all-reduce.N`, never with a
# space before it and a parenthesis after.
ALL_REDUCE = re.compile(r"\sall-reduce(?:-start|-done)?\(")


def is_all_reduce(op_name: str) -> bool:
    return bool(ALL_REDUCE.search(op_name.partition(" = ")[2]))


def read(run):
    if run.trace is None:
        return None
    shares = []
    for d, dev in enumerate(run.trace.devices):
        spent = sum(e - s for _, s, e in run.trace.calls(d, DECODE))
        if spent <= 0:
            continue
        ar = sum(e - s for name, s, e, mod in dev.ops if mod == DECODE and is_all_reduce(name))
        shares.append(ar / spent)
    return 100.0 * float(np.mean(shares)) if shares else None
