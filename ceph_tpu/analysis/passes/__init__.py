"""The pass inventory.  A pass is a callable ``(SourceTree) ->
list[Finding]`` carrying ``PASS_ID`` and ``DESCRIBE`` attributes; adding
one means writing the module, importing it here, and appending to
``ALL_PASSES`` (docs/TESTING.md "Adding a pass")."""

from __future__ import annotations

from .donation import DonationLifetimePass
from .exceptions import ExceptionSwallowPass
from .ledger import LedgerDisciplinePass
from .locks import LockDisciplinePass
from .options_coherence import OptionsCoherencePass
from .purity import JitPurityPass
from .section_await import SectionAwaitPass

ALL_PASSES = [
    DonationLifetimePass(),
    JitPurityPass(),
    ExceptionSwallowPass(),
    LockDisciplinePass(),
    OptionsCoherencePass(),
    LedgerDisciplinePass(),
    SectionAwaitPass(),
]

PASS_BY_ID = {p.PASS_ID: p for p in ALL_PASSES}
