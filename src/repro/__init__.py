"""repro: distributed tree-GGM structure learning + multi-pod JAX framework."""
