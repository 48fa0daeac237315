"""BAD: obs= without a None default (forces every caller to build an
Observability), span/metric names off the docs/observability.md grammar,
and a scope name computed at run time (rule obs-contract)."""
import jax


def run_engine(cfg, obs):
    with obs.tracer.span("DecodeStep"):
        obs.registry.observe("decode latency", 1.0)
    with jax.named_scope(cfg.name):
        pass
