"""Static analysis of the engine matrix's jitted programs.

Everything here works on TRACED jaxprs / lowered computations — nothing
executes on device. The package answers, per engine configuration and
before any benchmark runs:

  * what collectives does each fixpoint round issue, at what payload
    (``walker`` + the ``collective_budget`` rule vs the committed
    ``budgets/<engine>.json`` manifests),
  * does the batch program smuggle a host round-trip or an un-donated
    large output (``host_sync``),
  * do the buffers ``apply_batch`` declares donated actually alias in
    the lowered computation (``donation``),
  * can an int64 sentinel (1 << 62) reach an int32 truncation
    (``dtype_policy``),
  * how many jit variants can the (window, frontier-cap) planners ever
    key (``recompile_surface``),
  * what does each program keep live per device — symbolic peak /
    per-round / at-rest / donated byte formulas, plus the
    no-replicated-O(n)-buffer policy for the range layouts
    (``memory_budget``, ``memory.py``),

plus an AST lint of the sync-free planning path (``hostlint``). CLI:
``python -m repro.analysis.audit --engine all``; see docs/DESIGN.md §5.
"""
from .audit import (  # noqa: F401
    BUDGET_DIR,
    SCHEMA,
    audit_engines,
    generate_budget,
    load_budget,
    make_check,
    make_report,
    write_budgets,
)
from .hostlint import LintFinding, lint_file  # noqa: F401
from .memory import (  # noqa: F401
    generate_memory_section,
    profile_program,
    program_body,
    replicated_vertex_sites,
)
from .programs import (  # noqa: F401
    ENGINE_CONFIGS,
    AuditParams,
    EngineConfig,
    TracedEngine,
    trace_engine,
    trace_promotion_round,
    trace_removal_round,
)
from .rules import (  # noqa: F401
    RULES,
    Finding,
    cross_check_round,
    eval_formula,
    guess_formula,
    run_rules,
    split_round_collectives,
    tainted_truncations,
)
from .walker import (  # noqa: F401
    COLLECTIVE_PRIMS,
    CollectiveSite,
    Site,
    collectives,
    count_collectives,
    iter_sites,
    primitive_names,
)
