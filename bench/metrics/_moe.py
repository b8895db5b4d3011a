"""Device seconds of the expert layer in a reduced trace.

The program's ``moe/`` scopes name the expert layer's ops, except the
TPU's grouped matmul: its ``ragged-dot-*`` kernels carry the kernel's
name in place of the program's scope, so they are found by that name.
"""
import re

from harness.trace import scope_seconds

_KERNEL = re.compile(r"(^|/)ragged-dot[\w-]*$")


def kernel_seconds(red: dict) -> float:
    """Grouped-matmul kernel ops that no ``moe/`` scope already holds."""
    return sum(v for k, v in red["op_s"].items()
               if _KERNEL.search(k) and "moe/" not in k)


def expert_seconds(red: dict) -> float:
    """The held experts' grouped matmuls: ``moe/experts`` and the kernel."""
    return scope_seconds(red, "moe/experts") + kernel_seconds(red)


def layer_seconds(red: dict) -> float:
    """The whole expert layer: every ``moe/`` scope and the kernel."""
    return scope_seconds(red, "moe") + kernel_seconds(red)
