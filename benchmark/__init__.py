"""The served-path benchmark of selkies-tpu (see README.md here)."""
