#!/usr/bin/env python3
"""``scope_table.py`` with the cell's own device scopes added to the ones it
groups by. The names are data: ``trace.scopes`` of the cell's workload file
(``ling3-flash-serve-reasongen``: ``kda_chunk`` (prefill), ``kda_step``
(decode), ``mla_absorbed``, ``moe_route``, ``moe_experts``).
``scope_table.SCOPES`` is a fixed tuple in a file this PR may not edit; a
``benchmark`` issue makes it read the workload file itself and removes this
tool (PERF.md §7). The run class is handed over the way ``scope_table.main``
hands its own to ``runtime``.

    python3 benchmark/tools/scope_table_hybrid.py --workload <cell> --seed <n> --seconds <s>
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.tools import scope_table  # noqa: E402


class CellScopeRun(scope_table.ScopeRun):
    def reduce_trace(self, **kw):
        own = tuple(self.workload["trace"].get("scopes", ()))
        self.tool = dict(self.tool, scopes=scope_table.SCOPES + own)
        return super().reduce_trace(**kw)


if __name__ == "__main__":
    scope_table.ScopeRun = CellScopeRun
    sys.exit(scope_table.main())
