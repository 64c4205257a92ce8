"""How the Pallas kernels run, and which attention path each graph took.

Two rules, both explicit:

* **Interpret mode is asked for, never inferred.** A kernel call site passes
  ``interpret=pallas_interpret()``, which is True only when the process
  requested it (:func:`request_interpret` — the test conftest and
  ``compat.force_cpu_devices`` do; the serving path on a TPU never does).
  Nothing consults the process's default backend: a process whose TPU
  failed to come up fails at the first kernel compile instead of quietly
  emulating it.
* **A declined kernel leaves a record.** Call sites :func:`note` the path
  they took (``pallas`` / ``pallas-interpret`` / ``xla`` + why) at trace
  time; the application collects the notes of its own graphs
  (:func:`recording`) and serves them in ``warmup_state()["kernels"]`` —
  i.e. ``/v1/debug/state`` and the precompile report.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
from typing import Optional, Set, Tuple

INTERPRET_ENV = "NXDI_TPU_PALLAS_INTERPRET"

_SINK: contextvars.ContextVar[Optional[Set[Tuple[str, str, str]]]] = \
    contextvars.ContextVar("nxdi_kernel_notes", default=None)


def request_interpret() -> None:
    """Ask for interpret-mode Pallas kernels in this process and every
    child it starts (the request rides the environment)."""
    os.environ[INTERPRET_ENV] = "1"


def pallas_interpret() -> bool:
    return os.environ.get(INTERPRET_ENV) == "1"


def kernel_path() -> str:
    """The label a call site notes when it takes its kernel."""
    return "pallas-interpret" if pallas_interpret() else "pallas"


@contextlib.contextmanager
def recording(sink: Set[Tuple[str, str, str]]):
    """Collect the :func:`note` calls of everything traced in the body."""
    token = _SINK.set(sink)
    try:
        yield
    finally:
        _SINK.reset(token)


def note(site: str, path: str, reason: str = "") -> None:
    sink = _SINK.get()
    if sink is not None:
        sink.add((site, path, reason))


def experts_path(notes) -> str:
    """The expert path a program took, from the :func:`note` triples its
    trace left (``modules/moe.py`` writes them): "walk" (the kernel over the
    touched experts), "ragged" (the grouped matmuls), "dense" (all experts
    in an einsum), "mixed", or "none" (no routed block)."""
    paths = {"ragged" if site == "moe_ragged"
             else "dense" if path == "xla" else "walk"
             for site, path, _ in notes
             if site in ("moe_ragged", "moe_decode")}
    return paths.pop() if len(paths) == 1 else "mixed" if paths else "none"


def state_on_kernel(notes) -> bool:
    """Whether a program stepped its recurrent state on the state-step
    kernel, from the :func:`note` triples its trace left
    (``models/model_base.py`` ``run_layers_ssm`` writes them)."""
    return any(site == "recurrent_state" and path != "xla"
               for site, path, _ in notes)


def prefill_attn_on_kernel(notes) -> bool:
    """Whether a chunk program ran its attention on a prefill kernel, from
    the :func:`note` triples its trace left (``ops/mla_prefill.py``
    ``chunk_attention`` writes them)."""
    return any(site == "mla_prefill" and path != "xla"
               for site, path, _ in notes)


def paged_prefill_on_kernel(notes) -> bool:
    """Whether a chunk program attended over the K / V pools on the paged
    prefill kernel, from the :func:`note` triples its trace left
    (``ops/paged_prefill.py`` ``chunk_attention`` writes them)."""
    return any(site == "paged_prefill" and path != "xla"
               for site, path, _ in notes)


def second_decoder_apart(notes) -> bool:
    """Whether a chunk program of a decoder-hybrid-decoder stops its walk
    before the second decoder and runs that for the sampled token alone,
    from the :func:`note` triples its trace left
    (``models/model_base.py`` ``second_decoder_tokens`` writes them)."""
    return any(site == "second_decoder" for site, _, _ in notes)


def select_on_kernel(notes) -> bool:
    """Whether a program of a stack with a learned sparse selection scored
    and searched on the selection kernel, from the :func:`note` triples its
    trace left (``ops/index_select.py`` ``select_of`` writes them)."""
    return any(site == "index_select" and path != "xla"
               for site, path, _ in notes)
