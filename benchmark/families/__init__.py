"""Model families: how a configuration file becomes the program's model and
its plain reference. One module per family, found by the file's `family`."""
