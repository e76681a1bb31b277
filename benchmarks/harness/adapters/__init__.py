"""One adapter per program family: how the harness reaches into the system
under test from outside (hooks, overrides) and how its first steps are handed
to the plain reference. A configuration file names its adapter."""
