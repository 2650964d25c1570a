"""The system under test, as the benchmark drives it: the program's own
serving engine and stream server, built from a configuration file's sizes.
The only file of the benchmark that imports the program."""

from __future__ import annotations

#: the program's Pallas kernels, by the names their device events carry
STEP_KERNEL = "lstm_stack_step"
WAVEFRONT_KERNEL = "lstm_stack_wavefront"


def autoencoder_config(cfg: dict):
    from repro.core.autoencoder import AutoencoderConfig

    return AutoencoderConfig(
        input_dim=cfg["input_dim"], hidden=tuple(cfg["hidden"]),
        latent_boundary=cfg["latent_boundary"], timesteps=cfg["timesteps"])


def build_engine(params: dict, cfg: dict):
    """``StreamingAnomalyEngine`` as a user serves the model: one stream
    per slot (``batch=1``), the ``fused_step`` backend, default knobs."""
    from repro.serve.engine import StreamingAnomalyEngine

    engine = StreamingAnomalyEngine(
        params, autoencoder_config(cfg), batch=1, impl=cfg["impl"],
        tune=cfg["tune"])
    if engine.effective_impl != cfg["impl"]:
        raise RuntimeError(
            f"engine fell back to {engine.effective_impl!r}: "
            f"{engine.fallback_reason}")
    return engine


def build_server(engine, server: dict, on_score):
    from repro.serve.server import ServerConfig, StreamServer

    return StreamServer(engine, ServerConfig(**server), on_score=on_score)
