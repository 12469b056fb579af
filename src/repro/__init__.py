"""Hoplite reproduction package.

The pure-python core (``repro.core``, ``repro.runtime``, ``repro.serve``)
imports without jax; the device path (``collectives``, ``train``,
``launch``) targets the installed jax (0.9) directly.
"""
