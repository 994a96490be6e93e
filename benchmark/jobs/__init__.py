"""One module per kind of job, found by a traffic mix's ``job``; each gives
``run(window.Run) -> window.Result``."""
