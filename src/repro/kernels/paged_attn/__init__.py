# the autotune *submodule* stays addressable (autotune.autotune(...) runs
# a sweep); its data types are re-exported flat
from . import autotune  # noqa: F401
from .autotune import (Candidate, Geometry,  # noqa: F401
                       enumerate_candidates, entry_key, load_entries,
                       make_workload, prune, resolve_cache_path,
                       save_entries)
from .kernel import pack_pages, page_shape  # noqa: F401
from .ops import (PagedAttnTelemetry, amenability_reports,  # noqa: F401
                  attn_telemetry,
                  paged_attn, paged_attn_xla,
                  paged_prefill_attn, paged_prefill_attn_pallas,
                  paged_verify_attn)
from .ref import (gather_pages, paged_attn_ref,  # noqa: F401
                  paged_prefill_attn_ref)
