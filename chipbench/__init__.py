"""The chip benchmark of the LSMGraph store; ``run.py`` is its command."""
