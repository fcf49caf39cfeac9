"""The one import point for the jax surface outside ``repro.models``.

Modules outside the jax-containment allowlist (see the ``jax-containment``
rule of :mod:`repro.core.warpsim.lint`) bind jax through here, so new jax
surface is reviewed in one place. Also home to the process-level jax
settings every entry point shares: the persistent compilation cache and
the "is a backend up?" check that keeps forked pools away from a process
that holds a device.
"""

from __future__ import annotations

import os

import jax

#: Fixed in-checkout compile-cache directory, used when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset. The path is part of the cache
#: key, so it never depends on a tempdir, a pid or the time.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def shard_map(f, mesh, in_specs, out_specs, **kw):
    """``jax.shard_map`` with keyword mesh/specs."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def tpu_compiler_params(**kw):
    """``pltpu.CompilerParams`` (import deferred to call)."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(**kw)


def pallas():
    """The ``jax.experimental.pallas`` module (import deferred to call)."""
    from jax.experimental import pallas as pl
    return pl


def jax_modules():
    """``(jax, jax.numpy, jax.sharding)`` via the blessed import point.

    Modules outside the jax-containment allowlist (``compat.py``,
    ``warpsim/_pallas.py`` — see the ``jax-containment`` rule of
    :mod:`repro.core.warpsim.lint`) must not ``import jax`` directly;
    they bind the modules from here instead, so new jax surface is
    reviewed in one place.
    """
    import jax.numpy
    import jax.sharding
    return jax, jax.numpy, jax.sharding


def init_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and
    nothing else is set here. Otherwise the cache goes to the fixed
    :data:`COMPILE_CACHE_DIR` inside the checkout. Call before the first
    compile of the process. Also installs :func:`annotate_stages`, since
    every process that runs on the chip passes through here.
    """
    annotate_stages()
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def annotate_stages() -> None:
    """Put warpsim's host stages, spans and device holds into profiler
    traces as ``warpsim.<name>`` annotations, on the device's clock.
    With no trace running an annotation costs well under a microsecond."""
    from repro.core.warpsim import obs

    obs.set_annotation_factory(jax.profiler.TraceAnnotation)


def backend_initialized() -> bool:
    """True once this process has brought up a jax backend (and so may
    hold a device): forking it then risks a deadlock, or a child that
    fights the parent for the chip."""
    from jax._src import xla_bridge
    return xla_bridge.backends_are_initialized()
