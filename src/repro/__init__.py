"""repro: off-path SmartNIC characterization, rebuilt for TPU meshes."""
