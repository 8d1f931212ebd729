"""A test-only family: Mamba2's hooks, with its kernels under other names
and a limit of its own, to show that a family plugs in from its own file."""

from bench.families import mamba2

PREFIX = "renamed_"

WIDTHS = mamba2.WIDTHS
LIMITS = {"mismatch_share": 0.0}
STEP_KERNEL = PREFIX + mamba2.STEP_KERNEL
make_params = mamba2.make_params
calibrate = mamba2.calibrate
build = mamba2.build
warm_up = mamba2.warm_up
table_itemsize = mamba2.table_itemsize
bytes_per_step = mamba2.bytes_per_step
gaps = mamba2.gaps
dense_flops_per_token = mamba2.dense_flops_per_token
compile_step = mamba2.compile_step


def kernel_calls(conf, rows, itemsize):
    return {group: {PREFIX + k: calls for k, calls in kernels.items()}
            for group, kernels in
            mamba2.kernel_calls(conf, rows, itemsize).items()}
