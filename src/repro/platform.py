"""Public export surface for the unified control-plane API.

    from repro.platform import Platform, PlatformConfig

``Platform.build(scenario=..., config=...)`` assembles a validated
control plane (world, cluster, scheduler, autoscaler, simulation,
observer hub) from a ``PlatformConfig`` tree or a plain manifest dict;
schedulers / scenario kinds / trace programs / routers are selected
through name-based registries, and the autoscaler and simulator consume
their collaborators only through the capability protocols
(``CapacityProvider``, ``ReleasePicker``, ``LogicalStartPicker``,
``Router``) — see ``repro.core.platform``.

    python -m repro.platform        # CI smoke: every registered
                                    # scheduler x one scenario, built
                                    # from pure config dicts, 30 ticks
"""
from .core.platform import *            # noqa: F401,F403
from .core.platform import __all__      # noqa: F401


def main() -> None:
    from .compile_cache import enable_compile_cache
    from .core.platform import smoke
    enable_compile_cache()
    smoke()


if __name__ == "__main__":
    main()
