"""The chip benchmark of horovod_tpu: cells, metrics and their yardsticks.

See README.md in this directory.  Nothing here is imported by the
package; the benchmark imports the package.
"""
