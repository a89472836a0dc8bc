from .lm import (init_model, apply_model, init_cache, init_paged_cache,
                 supports_paged_cache)
