"""GOOD: obs defaults to None (zero-cost un-observed) and the names follow
the grammar: spans <subsystem>.<signal>, metrics <subsystem>/<signal>,
named scopes literal [a-z][a-z0-9_]*."""
import jax


def run_engine(cfg, obs=None):
    if obs is not None:
        with obs.tracer.span("serve.decode_step"):
            obs.registry.observe("serve/decode_latency_s", 1.0)
    with jax.named_scope("decode"):
        pass
