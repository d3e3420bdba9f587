"""One adapter per architecture: how a configuration file becomes the system
under test (through the program's normal entry points), its plain reference,
and its operation counts. A configuration names its adapter in `model`."""
