"""Where the checkout keeps what the program reads beside its own code.

Everything the entry points, benches and tests open lives under the repo:
the chip machine receives a copy of the checkout and nothing else.
"""

from __future__ import annotations

import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# query suites in the reference's text form and directory layout
# (its scripts/sparql_query/): queries/lubm/basic/lubm_q1 ...
QUERIES = os.path.join(REPO, "queries")
LUBM_BASIC = os.path.join(QUERIES, "lubm", "basic")
LUBM_EMULATOR = os.path.join(QUERIES, "lubm", "emulator")
