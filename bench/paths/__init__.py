"""One module per user path of the system; a traffic file names its path."""
