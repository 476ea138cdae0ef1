from .engine import (Scheduler, PagedScheduler, Request,
                     make_prefill_step, make_decode_step,
                     make_chunked_decode_loop,
                     make_admit_fn, make_paged_decode_loop,
                     make_paged_admit_fn, init_slot_pool, latency_stats,
                     percentile, greedy_sample)  # noqa: F401
from .trace import (poisson_arrivals, bursty_arrivals, make_trace,
                    load_trace, save_trace, validate_trace,
                    TraceError)  # noqa: F401
from .manifest import AuditedEntry  # noqa: F401
from . import manifest  # noqa: F401
