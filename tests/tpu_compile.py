"""What the ``test_tpu_compile_*`` files share: programs and kernels compiled
for a described v5e, with no chip attached, one file a cache family so that
the test runner can give each a worker of its own. A compile that passes is
not a chip run: nothing in those files is a time.

The topology is described inside a fixture (``one_chip``, tests/conftest.py),
never while a module is imported: a worker that is handed none of those files
never loads the TPU's library (``on-chip-measurement`` guide). Several workers
load it at once only under ``ALLOW_MULTIPLE_LIBTPU_LOAD=1``, which the
driver's command sets; without it the first worker's files run and the
others' skip.
"""

from __future__ import annotations

import inspect
import re

import pytest

from ditl_tpu.infer.continuous import ContinuousEngine
from ditl_tpu.infer.page_format import tail_width

# The tail widths the engines build: the serving cells' (the constructor's
# default ``decode_chunk``, a 4-step program in an 8-column tail) and
# ``benchmarks/paged_check.py``'s 16-step ticks.
_CHUNK = inspect.signature(ContinuousEngine).parameters["decode_chunk"].default
over_tails = pytest.mark.parametrize(
    "tail", [tail_width(_CHUNK), tail_width(16)], ids=[f"tick-{_CHUNK}", "tick-16"])

_GIB = 2 ** 30
_TENTH_SPARE = 0.9 * 15.75 * _GIB  # of a v5e's 15.75 GiB


def _instructions(text: str) -> set[str]:
    return {m.split(".")[0] for m in re.findall(r"%([\w\-.]+) = [^\n]*custom-call", text)}


def _steps(starts, alive, ps, maxp, window=None, group=1):
    """The kernels' work list, built in the compiled program as a decode
    program builds it (``decode_steps``: rows, ks and the traced count that
    is the grid's length; ``group``: the K/V kernel's ``pages_a_step``)."""
    from ditl_tpu.ops.paged_attention import decode_steps

    return decode_steps(starts, alive, page_size=ps, max_pages=maxp, window=window,
                        group=group)


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of nested jaxprs included (a
    ``pallas_call``'s kernel, the branches of a ``pl.when``)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in val if isinstance(val, (tuple, list)) else (val,):
                sub = getattr(sub, "jaxpr", sub)  # ClosedJaxpr -> Jaxpr
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _total_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.temp_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes)
