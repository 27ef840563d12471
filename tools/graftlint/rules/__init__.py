"""Rule registry: importing this package registers every rule family.

Codes are grouped by family:
  GL1xx  trace safety       (imports that must route through compat,
                             host ops inside jitted functions)
  GL106  MXU dot hygiene    (preferred_element_type on every MXU dot)
  GL107  buffer donation    (reads of donate_argnums arguments after
                             the jitted call)
  GL114+ concurrency        (context-colored: blocking calls in async
                             context, locks held across blocking ops or
                             compiled dispatch, fire-and-forget tasks,
                             stale suppressions)
  GL121+ locksets           (per-object lock identity: inconsistent-
                             guard data races, lock-order cycles,
                             guarded-collection escapes)
  GL124  unvalidated-committed-json (hygiene family, tools/ included)
  GL3xx  Pallas bounds      (unclamped dynamic indexing, tile shapes)
  GL4xx  repo hygiene       (bare except, mutable defaults, import-time env)
"""
from . import trace_safety    # noqa: F401
from . import mxu             # noqa: F401
from . import donation        # noqa: F401
from . import pallas_bounds   # noqa: F401
from . import hygiene         # noqa: F401
from . import concurrency     # noqa: F401
from . import locksets        # noqa: F401
