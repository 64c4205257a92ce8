"""The scratch kinds the benchmark's cells step, at toy sizes.

Four of the seven cells step rows in SLOT order (``_SlotScratch``): the
recurrent stacks (granite, olmo-hybrid, qwen3-next) and the window-pool
stack (smallthinker). The other three step live rows first
(``_PagedScratch``). A serving test that holds for "the adapter" is
parametrised over ``stack_app``: ``SLOT_STACKS`` beside the test module's
own attention app, so it runs both scratch classes and both reasons a stack
holds a slot. The toys are the fixtures of ``tests/test_recurrent_paged.py``
and ``tests/test_smallthinker_paged.py`` (vocab 128, four rows).
"""

import pytest

import test_recurrent_paged as granite_toy    # puts benchmark/ on sys.path
import test_smallthinker_paged as window_toy
from harness import build, weights


def recurrent_app(**serve):
    """The granite toy stack (Mamba-2 mixers): seq_len 96, chunks of 8/16."""
    ref = build.load_reference("granitemoehybrid")
    w = weights.make_weights(ref.weight_shapes(granite_toy.HF),
                             seed=2**31 + 30)
    return granite_toy._app(ref, w, **serve)


def window_pool_app(**serve):
    """The smallthinker toy stack (a KV pool by layer kind, rings of 7
    pages): seq_len 256, chunks of 8/32."""
    ref = build.load_reference("smallthinker")
    w = weights.make_weights(ref.weight_shapes(window_toy.HF),
                             seed=2**31 + 43)
    return window_toy._app(ref, w, **serve)


SLOT_STACKS = {"recurrent": recurrent_app, "window_pool": window_pool_app}


@pytest.fixture(scope="module", params=["attention", *SLOT_STACKS])
def stack_app(request):
    """An app of each scratch kind: live rows first (``_PagedScratch``: the
    importing module's own ``paged_app``), and rows in slot order for each
    reason a stack holds a slot. Import it into the test module."""
    if request.param == "attention":
        return request.getfixturevalue("paged_app")
    return SLOT_STACKS[request.param]()
