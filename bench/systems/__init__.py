"""How the benchmark drives one kind of system: deploy it, put the
benchmark's weights in, build the arrivals, warm up, serve the window, and
check what came out against the configuration's plain reference.  A
configuration names its system in its ``"system"`` key."""
