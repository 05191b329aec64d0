"""Serving package: the continuous-batching engine, preemption scheduler,
copy-on-write prefix caching and the self-speculative decoding helpers
(drafting + rejection sampling)."""
from repro.serve.engine import (EngineConfig, PageAllocator, Request,
                                Scheduler, ServeEngine, SwapPool,
                                generate_sequential,
                                make_mixed_requests)
from repro.serve.prefix_cache import PrefixCache, PrefixNode
from repro.serve.speculative import (LinearDrafter, NGramDrafter,
                                     greedy_accept, ngram_propose,
                                     rejection_sample)
