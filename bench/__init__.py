"""The on-chip benchmark of Jiagu's control plane (see PERF.md)."""
