# Launchers: the mesh builders and the CWS-orchestrated training driver.
