"""repro: consistent distributed mesh-based GNNs in JAX (SC24-W reproduction
+ TPU-pod framework). See ROADMAP.md / CONTRIBUTING.md."""

__version__ = "1.0.0"
