"""How each model family's configuration file becomes the program's
``ModelConfig``: one module per family, found by the file's ``family``."""
